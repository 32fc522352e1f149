"""Model operations of the work completed in the window over the chips'
bf16 peak, in %: for each request whose first token was streamed in the
window, the predictor over its query and the fuser's encoder over its
prompt; for each token streamed in the window, one fuser decode position.
Only tokens that carry content are counted."""

from harness import flops, fuser_shape, predictor_shape, yardstick


def read(ctx):
    if ctx.peaks is None:  # no chip, no peak to share
        return None
    t0, t1 = ctx.window
    cfg = ctx.cfg
    f, p = fuser_shape(cfg), predictor_shape(cfg)
    total = 0.0
    for seq, times in ctx.per_request.items():
        s = ctx.served.get(seq)
        if s is None:
            continue
        query = yardstick.encode(s.query.query)[:cfg["max_query_len"]]
        answers = [yardstick.encode(s.member_texts[j])[:s.cap]
                   for j in range(len(s.mask)) if s.mask[j]]
        enc_tokens = min(len(query) + sum(1 + len(a) for a in answers), cfg["max_fusion_len"])
        if t0 <= min(times) <= t1:
            total += flops.predictor_request_flops(
                p, min(1 + len(query), cfg["max_query_len"]), len(s.mask),
                2 * cfg["predictor_position_buckets"])
            total += flops.fuser_request_flops(f, enc_tokens, 0)
        total += sum(flops.fuser_decode_step_flops(f, 1, enc_tokens, k)
                     for k, t in enumerate(sorted(times)) if t0 <= t <= t1)
    return 100.0 * total / ((t1 - t0) * ctx.chips * ctx.peaks["bf16_flops_per_s"])
