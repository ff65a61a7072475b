"""Chip benchmark of the LoRAServe serving path: cells of a model
configuration and a traffic mix, measured end to end and by layer on a
TPU. ``python3 chipbench/run.py --help`` says how to run one cell."""
