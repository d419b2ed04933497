# Pallas kernels for the compute hot spots, each with a jnp oracle in
# ref.py. The platform picks the lowering: compiled on TPU, interpreted
# elsewhere.
