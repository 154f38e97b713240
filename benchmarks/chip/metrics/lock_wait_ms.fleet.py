"""Time the serve engine waited to acquire its state lock, per step, in ms.

The sum of ``serve.lock_wait`` spans (the acquire alone, in the pump and
in each step's record) over the number of ``serve.step`` spans.  None where
the trace holds neither.
"""


def read(trace, record):
    spans = trace["spans"]
    wait, step = spans.get("serve.lock_wait"), spans.get("serve.step")
    if not wait or not step:
        return None
    return 1e3 * sum(wait) / len(step)
