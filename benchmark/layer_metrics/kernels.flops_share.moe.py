"""``kernels.flops_share`` in the sparse-expert scoring cell: the driver's
``model_flops_in_window`` there is the admissions' (real prompt tokens, the
active experts only)."""
from harness.spec import load_module

read = load_module("layer_metrics", "kernels.flops_share").read
