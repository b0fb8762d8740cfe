"""Device: the share of the traced window in which no op ran, mean
over the cell's chips."""


def read(rec):
    devs = rec["trace"]["devices"]
    if not devs:
        return None
    w = rec["trace"]["window_s"]
    return sum(1.0 - d["busy_s"] / w for d in devs.values()) / len(devs) \
        * 100.0
