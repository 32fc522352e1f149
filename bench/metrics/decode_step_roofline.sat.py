"""Share of the least time a fuser decode step needs: the larger of its
bytes (decoder weights and every slot's cross-attention cache) over HBM
bandwidth and its operations (six bf16 passes each, float32 at "highest")
over the bf16 peak, divided by the measured device time per step.  Memory
bounds it at these shapes."""

from harness import flops, fuser_shape, trace


def read(ctx):
    if ctx.peaks is None:  # no chip, no peak to share
        return None
    runs = trace.per_span_program_ms(ctx.trace, "bench.decode")
    if runs is None:
        return None
    step_s = runs[0] / runs[1] / 1e3
    f = fuser_shape(ctx.cfg)
    rows, enc = ctx.cfg["stream_capacity"], ctx.cfg["max_fusion_len"]
    share, _bound = flops.roofline_share(
        flops.fuser_decode_step_flops(f, rows, enc, ctx.mix["max_new_tokens"] // 2),
        flops.fuser_decode_step_bytes(f, rows, enc, 4 if ctx.cfg["dtype"] == "float32" else 2), step_s, ctx.peaks,
        passes=ctx.peaks["f32_highest_passes"])
    return share
