"""``kernels.flops_share`` in the decode cell."""
from harness.spec import load_module

read = load_module("layer_metrics", "kernels.flops_share").read
