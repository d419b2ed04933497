# The one Pallas kernel on a main path (netes_fused_mixing: the int8 wire
# contraction and broadcast select), and ref.py's jnp oracles that tests
# compare the live paths against. The platform picks the kernel's
# lowering: compiled on TPU, interpreted elsewhere.
