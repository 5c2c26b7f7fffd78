"""Share of the traced window in which no operation ran on the device."""
NAME = "idle_share.train"
UNIT = "%"
LAYER = "device (TPU v5e)"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(ctx, peaks):
    tr = ctx.get("trace")
    if ctx.get("mode") != "train" or not tr or not tr["devices"] \
            or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
