"""Host time of one engine step outside its waits for the device, in ms:
over the steps of the traced serving window, the seconds of the program's
``serve.step`` spans (all of ``Engine.step``) less those of its
``serve.readback`` spans (every host read of the logits, which waits for
the device), over the number of ``serve.step`` spans
(``serve/engine.py``).  Read from the program's table of the spans a
profiler recorded (``repro.core.spans``)."""


def read(ctx):
    try:
        from repro.core import spans
    except ImportError:             # a program without spans
        return None
    got = spans.totals(traced=True)
    if "serve.step" not in got or "serve.readback" not in got:
        return None
    return (got["serve.step"]["total_s"] - got["serve.readback"]["total_s"]
            ) / got["serve.step"]["count"] * 1e3
