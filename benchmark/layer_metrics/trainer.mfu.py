"""Model FLOP/s utilisation: samples per second x training FLOP per sample
counted from the bound symbol (``benchmark/flops.py``: 2 x MACs x 3,
recomputation not credited), over chips x the chip's bf16 peak."""


def read(run):
    if run.peaks is None:
        return None
    rate = run.obs["samples"] / run.obs["elapsed_s"]
    return 100.0 * rate * run.obs["train_flops_per_sample"] / (
        run.chips * run.peaks["bf16_flops_per_s"])
