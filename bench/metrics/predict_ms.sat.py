"""Host milliseconds per batch inside the server's ``predict_quality``
(the eager quality predictor), over the window."""


def read(ctx):
    return ctx.span_ms("bench.predict")
