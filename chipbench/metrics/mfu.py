"""Model FLOP/s utilisation of the train step, in percent: model FLOPs per
pair (``flops.py``) times pairs/s per chip, over the chip's bf16 peak."""

RATE = "pairs_per_s"


def read(run, rate=RATE):
    peak, pairs = run.get("peak"), run["e2e"].get(rate)
    if not peak or not pairs:
        return None
    return 100.0 * run["flops_per_pair"] * pairs / peak["bf16_flops_per_s"]
