"""repro: a-Tucker (input-adaptive, matricization-free Tucker decomposition)
as a first-class feature of a multi-pod JAX LM training/serving framework.

Subpackages: core (the paper), kernels (Pallas TPU), models (10-arch zoo),
optim / train / serve / checkpoint / data (substrate), configs (assigned
architectures), launch (mesh + dry-run + drivers), roofline (HLO analysis).
"""

__version__ = "1.0.0"
