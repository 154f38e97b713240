"""Share of the traced window in which the serve engine's pump had nothing
to do, %: the sum of its ``serve.pump.wait`` spans (the poll sleep after a
pump that found no work) over the window.

0 where the pump never waited; None where the trace holds no ``serve.pump``
span (a program without pump spans).
"""


def read(trace, record):
    spans = trace["spans"]
    if not spans.get("serve.pump"):
        return None
    return 100.0 * sum(spans.get("serve.pump.wait", ())) / trace["window_s"]
