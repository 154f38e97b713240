"""Mean host time of the engine's ``serve.step`` spans, in ms.

The span runs from the batched step's dispatch to its
``block_until_ready``.
"""


def read(trace, record):
    spans = trace["spans"].get("serve.step")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
