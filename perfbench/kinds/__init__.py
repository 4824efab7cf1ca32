"""Runners of the traffic kinds, one module each, named by a traffic file's
``kind`` key. Each defines `Session(cell, seed, seconds)`: set-up in the
constructor, then `window`, `end_to_end`, `layer_inputs`, `release` and
`check`."""
