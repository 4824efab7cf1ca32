"""Per-layer metric readers, one file each, named `<metric>.py` and loaded
by path (`harness.load_reader`); the `_*_work.py` modules count a
layer's operations and bytes for them."""
