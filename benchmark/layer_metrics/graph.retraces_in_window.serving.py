"""``graph.retraces_in_window`` in the serving cells."""
from harness.spec import load_module

read = load_module("layer_metrics", "graph.retraces_in_window").read
