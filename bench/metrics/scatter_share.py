"""scatter_share.*: device time of scatter operations (and fusions
rooted in one) over all device operation time in the traced window, %."""


def read(record):
    t = record.get("trace")
    if not t or t["op_s"] <= 0:
        return None
    return 100.0 * t["scatter_s"] / t["op_s"]
