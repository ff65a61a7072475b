import os

# the benchmark's tests run on the CPU at a smoke size; the chip is the
# benchmark's own business
os.environ.setdefault("JAX_PLATFORMS", "cpu")
