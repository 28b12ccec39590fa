"""repro — a JAX/Pallas reproduction framework for DIANA
(Mishchenko et al., Distributed Learning with Compressed Gradient Differences).

Package layout: core/ (the paper's algorithm), models/, optim/, data/,
checkpoint/, configs/, kernels/ (Pallas), launch/ (mesh, train, serve, dryrun).
"""

__version__ = "0.1.0"
