"""Compile the main-path Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel with abstract inputs placed on one
chip of a described ``v5e:2x2`` topology and asserts that the TPU compiler
emitted a Mosaic kernel (``tpu_custom_call``).  What the chip's compiler
refuses — an unsupported primitive, a block shape off the (8, 128) tiling,
more SMEM or VMEM than a core holds — fails here, at no chip time.

Shapes are the paper's tm-mnist widths (784 features -> W = 49 literal
words, 2000 clauses padded to C = 2048, K = 10) at serving bucket and
training batch B = 512, with the default tilings; the training kernel
also at tm-fmnist's 5120 clauses and the training cell's batch of 256.
The schedule-table shapes are those ``compile_tm`` gives a tm-mnist bank
after one training epoch.  One factorized compile uses a tm-edge-xl term table, whose
whole-table VMEM scratch grows with the artifact.  The convolutional
kernel compiles at convcotm-mnist's published widths: 28x28 images, a
10x10 window (361 positions, 272 literals a patch), 128 clauses, K = 10,
at each of its image tilings.  Every autotune
candidate is compiled too: on the chip ``tune()`` would crash serving on
one that does not lower.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and xdist workers must all
collect the same tests.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.matador_tm import (CONVCOTM_MNIST, TM_EDGE_XL, TM_FMNIST,
                                     TM_MNIST)
from repro.kernels import autotune, conv_infer, fused_infer, fused_train
from repro.kernels import sparse_infer
from repro.kernels import term_infer

B = 512
C = TM_MNIST.n_clauses_total          # 2048
L = TM_MNIST.n_literals               # 1568
W = -(-L // 32)                       # 49
K = TM_MNIST.n_classes                # 10
# compile_tm tables of a tm-mnist bank after one epoch (1986 unique clauses)
SPARSE_JP, SPARSE_T = 448, 18
TERM_TP, TERM_W, TERM_JP, TERM_T = 14848, 8, 128, 32
# tm-edge-xl: 65536 clauses over 256 words; ~46K unique (word, value)
# terms for a bank with 20 includes per clause
XL_W = -(-TM_EDGE_XL.n_literals // 32)
XL_CP, XL_TP, XL_TERM_W = TM_EDGE_XL.n_clauses_total, 46592, 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def assert_lowers(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def dense_forward(sharding, **blocks):
    assert_lowers(
        sharding,
        lambda lw, iw, v, ne: fused_infer.fused_tm_forward(
            lw, iw, v, ne, **blocks),
        ((B, W), jnp.uint32), ((C, W), jnp.uint32), ((C, K), jnp.int32),
        ((C,), jnp.int32))


def train_delta(sharding, c=C, b=B, **blocks):
    assert_lowers(
        sharding,
        lambda ta, lits, lw, iw, y, kn, pt, pn, cc, pol, seed:
        fused_train.fused_tm_train_delta(
            ta, lits, lw, iw, y, kn, pt, pn, cc, pol, seed,
            p_act=1.0, p_inact=1.0 / TM_MNIST.s, **blocks),
        ((c, L), jnp.int8), ((b, L), jnp.uint8), ((b, W), jnp.uint32),
        ((c, W), jnp.uint32), ((b,), jnp.int32), ((b,), jnp.int32),
        ((b,), jnp.float32), ((b,), jnp.float32), ((c,), jnp.int32),
        ((c,), jnp.int32), ((), jnp.uint32))


def sparse_forward(sharding, *, early_exit=False, jp=SPARSE_JP, t=SPARSE_T,
                   block_c=sparse_infer.DEFAULT_BLOCK_C,
                   block_j=sparse_infer.DEFAULT_BLOCK_J,
                   block_s=sparse_infer.DEFAULT_BLOCK_S):
    shapes = [((B, W), jnp.uint32), ((C, jp), jnp.int32),
              ((C, K), jnp.int32), ((4, t), jnp.int32)]
    if early_exit:
        shapes.append(((t,), jnp.int32))
    assert_lowers(
        sharding,
        lambda lw, chain, v, tiles, *margin:
        sparse_infer.sparse_tm_forward_tables(
            lw, chain, v, tiles, block_c=block_c, block_j=block_j,
            block_s=block_s, tile_margin=margin[0] if margin else None),
        *shapes)


def factorized_forward(sharding, *, early_exit=False, w=W, cp=C,
                       tp=TERM_TP, term_w=TERM_W, jp=TERM_JP, t=TERM_T,
                       block_c=term_infer.DEFAULT_BLOCK_C,
                       block_j=term_infer.DEFAULT_BLOCK_J,
                       block_t=term_infer.DEFAULT_BLOCK_T,
                       block_s=term_infer.DEFAULT_BLOCK_S):
    shapes = [((B, w), jnp.uint32), ((tp, term_w), jnp.int32),
              ((cp, jp), jnp.int32), ((cp, K), jnp.int32),
              ((6, t), jnp.int32)]
    if early_exit:
        shapes.append(((t,), jnp.int32))
    assert_lowers(
        sharding,
        lambda lw, terms, chain, v, tiles, *margin:
        term_infer.factorized_tm_forward_tables(
            lw, terms, chain, v, tiles, block_t=block_t, block_c=block_c,
            block_j=block_j, block_s=block_s,
            tile_margin=margin[0] if margin else None),
        *shapes)


def test_fused_forward_lowers(one_chip):
    dense_forward(one_chip)


@pytest.mark.parametrize("c,b", [(C, B), (TM_FMNIST.n_clauses_total, 256)],
                         ids=["tm-mnist", "tm-fmnist"])
def test_fused_train_delta_lowers(one_chip, c, b):
    train_delta(one_chip, c=c, b=b)


@pytest.mark.parametrize("early_exit", [False, True],
                         ids=["plain", "tile_margin"])
def test_sparse_forward_lowers(one_chip, early_exit):
    sparse_forward(one_chip, early_exit=early_exit)


@pytest.mark.parametrize("early_exit", [False, True],
                         ids=["plain", "tile_margin"])
def test_factorized_forward_lowers(one_chip, early_exit):
    factorized_forward(one_chip, early_exit=early_exit)


def test_factorized_forward_lowers_edge_xl_term_table(one_chip):
    bt = term_infer.DEFAULT_BLOCK_T
    factorized_forward(one_chip, w=XL_W, cp=XL_CP, tp=XL_TP,
                       term_w=XL_TERM_W, jp=64,
                       t=XL_TP // bt + XL_CP // term_infer.DEFAULT_BLOCK_C)


def conv_forward(sharding, block_b):
    g, cp = CONVCOTM_MNIST.geometry, CONVCOTM_MNIST.n_clauses
    assert (g.positions, g.literals, cp) == (361, 272, 128)
    assert_lowers(
        sharding,
        lambda x, band, v: conv_infer.conv_tm_forward(
            x, band, v, geom=g, block_b=block_b),
        ((B, g.image_words), jnp.uint32),
        ((g.Pw * cp, g.contraction), jnp.int8),
        ((cp, CONVCOTM_MNIST.n_classes), jnp.int32),
    )


@pytest.mark.parametrize("block_b", [128, 256, 512])
def test_conv_forward_lowers(one_chip, block_b):
    conv_forward(one_chip, block_b)


CANDIDATES = (
    [("fused_infer", c) for c in autotune._DEFAULT_CANDIDATES]
    + [("fused_train", c) for c in autotune._TRAIN_CANDIDATES]
    + [("sparse_infer", c) for c in autotune._SPARSE_CANDIDATES]
    + [("term_infer", c) for c in autotune._TERM_CANDIDATES])


@pytest.mark.parametrize("kernel,cand", CANDIDATES,
                         ids=[f"{k}-{'x'.join(map(str, c))}"
                              for k, c in CANDIDATES])
def test_autotune_candidate_lowers(one_chip, kernel, cand):
    if kernel in ("fused_infer", "fused_train"):
        bb, bc, bw = cand
        run = dense_forward if kernel == "fused_infer" else train_delta
        run(one_chip, block_b=bb, block_c=bc, block_w=bw)
    elif kernel == "sparse_infer":
        bc, bj, bs = cand
        assert bc * bj <= autotune.SMEM_TILE_WORDS
        sparse_forward(one_chip, block_c=bc, block_j=bj, block_s=bs,
                       jp=-(-SPARSE_JP // bj) * bj)
    else:
        bc, bj, bt, bs, tw = cand
        tw = tw or 32                   # auto width: at most one word
        assert bc * bj + bt * tw <= autotune.SMEM_TILE_WORDS
        factorized_forward(one_chip, block_c=bc, block_j=bj, block_t=bt,
                           block_s=bs, term_w=tw, cp=max(C, bc),
                           tp=-(-TERM_TP // bt) * bt,
                           jp=-(-TERM_JP // bj) * bj)
