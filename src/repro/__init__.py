"""LORASERVE reproduction — rank- and demand-aware LoRA adapter placement
and routing for distributed LLM inference, as a full JAX framework.

Subpackages: core (the paper's contribution), controlplane (drift
detection + SLO-driven autoscaling), cluster (simulated servers +
calibrated cost model), serving (the cluster facade over simulated or
real JAX engines), lora, kernels (Pallas SGMV), models (10-arch zoo),
training, data, configs, launch, traces.
"""
__version__ = "1.0.0"
