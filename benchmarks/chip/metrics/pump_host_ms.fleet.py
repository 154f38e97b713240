"""Host time of the serve engine's pump outside the batched step, per step,
in ms.

(sum of ``serve.pump`` spans - sum of ``serve.step`` spans) / the number of
``serve.step`` spans: staging, packing, the first transfer, recording and
the pumps that found no work, charged to the steps of the window.  None
where the trace holds no ``serve.pump`` span (a program without pump spans).
"""


def read(trace, record):
    spans = trace["spans"]
    pump, step = spans.get("serve.pump"), spans.get("serve.step")
    if not pump or not step:
        return None
    return 1e3 * (sum(pump) - sum(step)) / len(step)
