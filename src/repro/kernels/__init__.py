"""Pallas TPU kernels (+ pure-jnp oracles) for the MATADOR datapath.

Inference kernels: fused_infer (the dense datapath — HCB chain + class-sum
adder bank in one pass, no fired matrix in HBM), sparse_infer (the
block-sparse chain schedule of a compiled artifact), term_infer (its
factorized shared-term schedule), anytime (their budgeted and early-exit
modes) and conv_infer (the convolutional TM).  Training: fused_train
(clause fire, feedback and TA delta in one pass).  Baseline: xnor_popcount
(BNN layer).  ``ops`` is the dispatch layer, and ``ops.engine_levels`` the
one place that decides which inference engine serves an artifact; ``ref``
holds the oracles the kernels are tested against and the last rung of the
ladder; ``autotune`` picks block tilings per (shape, backend) with an
on-disk cache.
"""
