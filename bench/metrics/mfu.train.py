"""Model FLOP/s utilization of the train step: tokens per second of the
traced window times the model's FLOPs per token (``bench/flops.py``), over
the chip's bf16 peak (``bench/peaks.json``)."""
NAME = "mfu.train"
UNIT = "%"
LAYER = "model step (models/, train/trainer.py)"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(ctx, peaks):
    if ctx.get("mode") != "train" or not ctx.get("tokens_per_s"):
        return None
    return (100.0 * ctx["tokens_per_s"] * ctx["flops_per_token"]
            / peaks["bf16_flops_per_s"])
