"""The benchmark's yardstick: what runs a cell and turns its observations,
spans, counters and trace into numbers. Driven by data (see ``spec.py``)."""
