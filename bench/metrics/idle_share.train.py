"""Share of the traced training window in which no op ran on the device."""


def read(record):
    tr = record.get("trace")
    if not tr or record["traffic"]["kind"] != "train":
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
