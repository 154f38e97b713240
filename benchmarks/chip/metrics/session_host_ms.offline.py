"""Mean host time of the session's ``interface.run_batched`` spans, in ms.

The span covers the call's dispatch (and, on the sparse impl, its
host-side precheck), not the device's work.
"""


def read(trace, record):
    spans = trace["spans"].get("interface.run_batched")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
