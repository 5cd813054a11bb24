"""``process.compiles_in_window`` in the serving cells, where a compile in
the window lands in a request's time to first token."""
from harness.spec import load_module

read = load_module("layer_metrics", "process.compiles_in_window").read
