"""What the readers of a step's pauses share: which spans ran on the serving
thread, and the name the program under test gives a pass of Python's
collector.

The program's tracer hooks ``gc.callbacks`` while it is installed and exports
the span's name (``cfk_tpu.telemetry.trace.GC_SPAN``).  With the name and no
such span in the window the hook was in and no pass ran: a reading of 0.  A
program without the name has no hook, and a reader reports nothing.
"""


def gc_span_name():
    """The program's name for a collector pass, or None where its tracer
    hooks no collector."""
    try:
        from cfk_tpu.telemetry import trace
    except ImportError:
        return None
    return getattr(trace, "GC_SPAN", None)


def on_serving_thread(spans):
    """The spans of the thread that ran the window's ``serve/batch`` spans:
    a pass or a wait elsewhere (the store's writer thread) is not inside a
    serve step."""
    tids = {e["tid"] for e in spans if e["name"] == "serve/batch"}
    return [e for e in spans if e["tid"] in tids]

