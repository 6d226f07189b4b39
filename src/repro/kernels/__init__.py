"""Pallas TPU kernels (+ pure-jnp oracles) for the MATADOR datapath.

Kernels: fused_infer (the whole inference datapath — HCB chain + class-sum
adder bank in one pass, no fired matrix in HBM), clause_eval (HCB chain),
class_sum (vote adders), ta_update (training feedback), xnor_popcount (BNN
baseline layer).  ``ops`` is the dispatch layer; ``ref`` holds the oracles
the kernels are tested against; ``autotune`` picks fused-kernel block
tilings per (shape, backend) with an on-disk cache.
"""
