"""Drivers: the host's part of sending one request, in ms: the entry's
conversion of the host batch and the enqueueing of its copy to the device
(the driver's ``h2d`` spans, by the host's clock), averaged over the window's
requests. With requests dispatched ahead it hides behind the device's time
per request and moves the rate only once it passes that; a synchronous loop
such as ``pipeline.run_inference_with_tile_encoder`` pays it on every batch."""


def read(metric, trace, window, ctx):
    spans = [end - start for name, start, end in ctx.spans.spans if name == "h2d"]
    if not spans:
        return None
    return 1e-6 * sum(spans) / len(spans)
