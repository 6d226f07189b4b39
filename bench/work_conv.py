"""Operations and bytes of a convolutional TM's (ConvCoTM) inference, from
its published sizes (``bench/configs/convcotm-mnist.json``), never from
the artifact or the engine that served.

With ``C`` clauses, ``P`` patch positions, ``Lp`` literals per patch and
``K`` classes, counted in the TM's matmul form (a multiply and an add, 2
operations, per term) against the chip's int8 peak, per image:

* ``2*C*P*Lp``: each clause's violated literals on each patch (the
  include bits times the negated patch literals);
* ``C*P``: the OR of each clause over the positions;
* ``2*C*K``: the weighted class sums.

Bytes per call are the least the algorithm must move through HBM: the
packed images in, the int32 class sums out, and the bank once (the packed
include bits and the int8 weights).
"""

from __future__ import annotations


def sizes(cfg: dict):
    """(C, P, Lp, K, image words) from the configuration's own sizes."""
    H, W, win = cfg["image_h"], cfg["image_w"], cfg["window"]
    P = (H - win + 1) * (W - win + 1)
    Lp = 2 * (win * win + (H - win) + (W - win))
    return cfg["n_clauses"], P, Lp, cfg["n_classes"], -(-H * W // 32)


def ops_per_image(cfg: dict) -> float:
    C, P, Lp, K, _ = sizes(cfg)
    return 2.0 * C * P * Lp + C * P + 2.0 * C * K


def bytes_per_call(cfg: dict, batch: int) -> float:
    C, _, Lp, K, Wi = sizes(cfg)
    return batch * (4 * Wi + 4 * K) + C * (4 * -(-Lp // 32) + K)
