"""Operations and bytes of the TM's work, from its published sizes.

Everything here is computed from the configuration's sizes (C clauses,
L = 2F literals, K classes) and the batch, never from the trained
artifact or from the engine that served.  So a kernel's roofline share
and the whole step's MFU read the same work whichever engine runs, and a
change of engine cannot move the yardstick.  C is the published clause
count (``n_classes * clauses_per_class``), without the program's padding.

Operations are counted in the TM's matmul form, one multiply and one add
(2 operations) per term, against the chip's int8 peak:

* inference, per sample: ``2*C*L`` for the clause violations (the include
  mask times the negated literals) and ``2*C*K`` for the vote fold, so
  ``2*C*(L + K)``;
* the fused training kernel, per sample: the same ``2*C*L`` to fire the
  clauses, and ``2*C*L`` to fold one sample's feedback into the delta of
  every automaton (a select and an add);  the hash draws that pick the
  feedback are the implementation's way to the same result and do not
  count;
* one training step, per sample: the inference pre-pass that gives the
  class sums, plus the fused training kernel: ``2*C*(L + K) + 4*C*L``.

An engine that skips excluded literals or whole clause tiles does less
work than this dense count, so its roofline share could in principle read
above 100%: only below about 8 us for a bucket of 512 on tm-mnist,
hundreds of times under any bucket measured so far.

Bytes are the least the algorithm must move through HBM: per inference
call the packed literals in and the class sums out, plus the packed
include bank and its votes once; per training step the automata read once
and written once, plus the packed literals.
"""

from __future__ import annotations


def _sizes(cfg: dict):
    C = cfg["n_classes"] * cfg["clauses_per_class"]
    L = 2 * cfg["n_features"]
    return C, L, cfg["n_classes"], -(-L // 32)


def infer_ops_per_sample(cfg: dict) -> float:
    C, L, K, _ = _sizes(cfg)
    return 2.0 * C * (L + K)


def infer_bytes_per_call(cfg: dict, batch: int) -> float:
    C, L, K, W = _sizes(cfg)
    return batch * (4 * W + 4 * K) + C * (4 * W + K)


def train_kernel_ops_per_sample(cfg: dict) -> float:
    C, L, _, _ = _sizes(cfg)
    return 4.0 * C * L


def train_kernel_bytes_per_step(cfg: dict, batch: int) -> float:
    C, L, _, W = _sizes(cfg)
    return 2.0 * C * L + batch * 4 * W


def train_ops_per_sample(cfg: dict) -> float:
    return infer_ops_per_sample(cfg) + train_kernel_ops_per_sample(cfg)


def roofline(ops: float, nbytes: float, seconds: float, peaks: dict):
    """(share in %, the bound: "compute" or "memory") of work done in
    ``seconds`` of kernel time."""
    t_ops = ops / peaks["int8_ops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return 100.0 * max(t_ops, t_mem) / seconds, (
        "compute" if t_ops >= t_mem else "memory")
