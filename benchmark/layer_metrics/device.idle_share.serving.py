"""``device.idle_share`` in the serving cells, where the host sets the pace."""
from harness.spec import load_module

read = load_module("layer_metrics", "device.idle_share").read
