"""The convolutional coalesced TM (ConvCoTM) against a plain reference.

A 12x12 image, a 4x4 window (81 positions, 64 literals a patch), 16
clauses over 3 classes, seeded random banks and weights: the patch
literals, the Pallas kernel in interpret mode, the oracle rung,
``compile_tm`` -> ``run_compiled``, the artifact's envelope, and requests
served end to end through ``Gateway`` + ``EngineLadder``.  The reference
below is numpy loops over the published equations (Tunheim et al.,
arXiv:2501.19347), sharing nothing with the program.
"""

from __future__ import annotations

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compiler, packetizer, tm
from repro.kernels import conv_infer, ops, ref
from repro.runtime import faults

CFG = tm.ConvTMConfig(image_h=12, image_w=12, window=4, n_clauses=16,
                      n_classes=3)
G = CFG.geometry
SEEDS = [0, 1, 2]


def ref_patch_literals(img: np.ndarray) -> np.ndarray:
    """(B, H, W) -> (B, P, Lp): pixels, row and column thermometers (bit
    i is 1 iff the coordinate exceeds i), then every negation."""
    H, W, win = G
    out = []
    for py in range(H - win + 1):
        for px in range(W - win + 1):
            rows = []
            for b in range(img.shape[0]):
                feats = list(img[b, py:py + win, px:px + win].reshape(-1))
                feats += [int(py > i) for i in range(H - win)]
                feats += [int(px > i) for i in range(W - win)]
                rows.append(feats + [1 - f for f in feats])
            out.append(rows)
    return np.array(out, np.int64).transpose(1, 0, 2)


def ref_class_sums(img, include, weights) -> np.ndarray:
    lits = ref_patch_literals(img)
    sums = np.zeros((img.shape[0], weights.shape[1]), np.int64)
    for b in range(img.shape[0]):
        for j in range(include.shape[0]):
            inc = include[j].astype(bool)
            if inc.any() and any(lits[b, p][inc].all()
                                 for p in range(lits.shape[1])):
                sums[b] += weights[j]
    return sums


def make_case(seed: int, n: int = 24):
    """Images, and a bank whose clauses mostly come from the images' own
    true literals (so they fire), one of them empty, one random."""
    rng = np.random.default_rng(seed)
    img = (rng.random((n, G.H, G.W)) < 0.3).astype(np.uint8)
    lits = ref_patch_literals(img[:4])
    include = np.zeros((CFG.n_clauses, G.literals), np.uint8)
    for j in range(CFG.n_clauses - 2):
        b, p = rng.integers(4), rng.integers(G.positions)
        true = np.flatnonzero(lits[b, p])
        include[j, rng.choice(true, rng.integers(2, 7), replace=False)] = 1
    include[-1] = rng.random(G.literals) < 0.05      # include[-2] stays empty
    weights = rng.integers(-127, 128, (CFG.n_clauses, CFG.n_classes))
    words = packetizer.pack_bits_np(img.reshape(n, -1))
    return img, words, include, weights


def ta_of(include):
    return np.where(include == 1, 0, -1).astype(np.int8)


@pytest.mark.parametrize("seed", SEEDS)
def test_patch_literals_match_reference(seed):
    img, words, _, _ = make_case(seed, n=5)
    got = np.asarray(packetizer.patch_literals(jnp.asarray(words), G))
    np.testing.assert_array_equal(got, ref_patch_literals(img))


@pytest.mark.parametrize("corner, y_bits, x_bits", [
    ((0, 0), 0, 0), ((0, 8), 0, 8), ((8, 0), 8, 0), ((8, 8), 8, 8),
    ((3, 5), 3, 5)])
def test_thermometer_bits_at_corners(corner, y_bits, x_bits):
    """Coordinate c sets exactly its first c bits; negations mirror them."""
    words = packetizer.pack_bits_np(np.zeros((1, G.H * G.W), np.uint8))
    lits = np.asarray(packetizer.patch_literals(jnp.asarray(words), G))[0]
    row = lits[corner[0] * G.Pw + corner[1]]
    npix, ny = G.win * G.win, G.H - G.win
    y, x = row[npix:npix + ny], row[npix + ny:G.features]
    np.testing.assert_array_equal(y, np.arange(ny) < y_bits)
    np.testing.assert_array_equal(x, np.arange(G.W - G.win) < x_bits)
    np.testing.assert_array_equal(row[G.features:], 1 - row[:G.features])


def _kernel(words, include, weights):
    band, votes = conv_infer.conv_operands(include, weights, G)
    return conv_infer.conv_tm_forward(
        jnp.asarray(words), jnp.asarray(band), jnp.asarray(votes), geom=G,
        interpret=True)


def _oracle(words, include, weights):
    return ref.conv_class_sums_ref(
        packetizer.patch_literals(jnp.asarray(words), G),
        jnp.asarray(include), jnp.asarray(weights))


def _compiled(engine):
    def run(words, include, weights):
        art = compiler.compile_tm(CFG, ta_of(include), weights=weights)
        return compiler.run_compiled(art, jnp.asarray(words), engine=engine,
                                     interpret=True)
    return run


RUNGS = {"kernel": _kernel, "oracle": _oracle,
         "compiled-conv": _compiled("conv"),
         "compiled-oracle": _compiled("oracle")}


@pytest.mark.parametrize("rung", sorted(RUNGS))
@pytest.mark.parametrize("seed", SEEDS)
def test_class_sums_match_reference(rung, seed):
    img, words, include, weights = make_case(seed)
    got = np.asarray(RUNGS[rung](words, include, weights))
    want = ref_class_sums(img, include, weights)
    assert (want != 0).any()
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("rung", sorted(RUNGS))
def test_empty_clause_votes_zero(rung):
    """An empty clause never fires, whatever its weights."""
    _, words, include, weights = make_case(0)
    include[0] = 0
    base = np.asarray(RUNGS[rung](words, include, weights))
    weights = weights.copy()
    weights[0] = [127, -127, 127]
    np.testing.assert_array_equal(
        np.asarray(RUNGS[rung](words, include, weights)), base)


@pytest.mark.parametrize("rung", sorted(RUNGS))
def test_first_maximum_wins_a_tie(rung):
    """Classes 1 and 2 always tie above class 0: the answer is 1."""
    _, words, include, _ = make_case(1)
    weights = np.zeros((CFG.n_clauses, CFG.n_classes), np.int64)
    weights[:, 1] = weights[:, 2] = 5
    sums = np.asarray(RUNGS[rung](words, include, weights))
    assert (sums[:, 1] == sums[:, 2]).all() and (sums[:, 1] > 0).any()
    fired = sums[:, 1] > 0
    np.testing.assert_array_equal(sums.argmax(-1)[fired], 1)


def test_compile_counts_and_dedup():
    """Empty clauses drop, identical rows merge with their weights summed,
    and the artifact's counters read the published geometry."""
    _, _, include, weights = make_case(2)
    include[3] = include[4]
    art = compiler.compile_tm(CFG, ta_of(include), weights=weights)
    st = art.stats
    assert (st.n_positions, st.n_patch_literals) == (81, 64)
    assert st.n_clauses_nonempty == CFG.n_clauses - 1
    assert art.n_unique == st.n_clauses_unique == CFG.n_clauses - 2
    merged = weights[3] + weights[4]
    assert any((row == merged).all() for row in art.votes)
    assert (st.weight_min, st.weight_max) == (art.votes.min(),
                                              art.votes.max())
    assert st.include_sparsity == 1 - include.sum() / include.size


def test_artifact_round_trip_and_envelope(tmp_path):
    _, words, include, weights = make_case(0)
    art = compiler.compile_tm(CFG, ta_of(include), weights=weights)
    path = art.save(str(tmp_path / "conv"))
    back = compiler.CompiledTM.load(path)
    assert back.geometry == art.geometry == (12, 12, 4)
    np.testing.assert_array_equal(back.include_words, art.include_words)
    np.testing.assert_array_equal(
        np.asarray(compiler.run_compiled(back, jnp.asarray(words),
                                         engine="oracle")),
        np.asarray(compiler.run_compiled(art, jnp.asarray(words),
                                         engine="oracle")))
    with faults.injected("artifact.bitflip"):
        bad = art.save(str(tmp_path / "rotten"))
    with pytest.raises(compiler.ArtifactError):
        compiler.CompiledTM.load(bad)


@pytest.mark.parametrize("breakage", ["geometry", "width", "weights"])
def test_validate_rejects_a_broken_conv_artifact(breakage):
    _, _, include, weights = make_case(0)
    art = compiler.compile_tm(CFG, ta_of(include), weights=weights)
    if breakage == "geometry":
        art.geometry = art.geometry._replace(win=13)
    elif breakage == "width":
        art.include_words = np.pad(art.include_words, ((0, 0), (0, 1)))
        art.word_ids = np.arange(art.include_words.shape[1], dtype=np.int32)
    else:
        art.votes = art.votes * 200
    with pytest.raises(compiler.ArtifactError):
        compiler.validate_artifact(art)


@pytest.mark.parametrize("engine", ["factorized", "sparse", "dense"])
def test_vanilla_engines_refuse_a_conv_artifact(engine):
    _, words, include, weights = make_case(0)
    art = compiler.compile_tm(CFG, ta_of(include), weights=weights)
    with pytest.raises(TypeError):
        compiler.run_compiled(art, jnp.asarray(words), engine=engine)


def test_conv_engine_refuses_a_vanilla_artifact():
    cfg = tm.TMConfig(n_features=8, n_classes=2, clauses_per_class=2)
    st = tm.init(cfg, __import__("jax").random.PRNGKey(0))
    art = compiler.compile_tm(cfg, st.ta_state)
    with pytest.raises(TypeError):
        compiler.run_compiled(art, jnp.zeros((4, 1), jnp.uint32),
                              engine="conv")


@pytest.mark.parametrize("use_kernel, want", [(True, ["conv", "oracle"]),
                                              (False, ["oracle"])])
def test_ladder_is_chosen_from_the_artifact(use_kernel, want):
    _, _, include, weights = make_case(0)
    art = compiler.compile_tm(CFG, ta_of(include), weights=weights)
    assert ops.engine_levels(art, use_kernel=use_kernel) == want
    cfg = tm.TMConfig(n_features=8, n_classes=2, clauses_per_class=2)
    st = tm.init(cfg, __import__("jax").random.PRNGKey(0))
    vanilla = compiler.compile_tm(cfg, st.ta_state)
    levels = ops.engine_levels(vanilla, use_kernel=use_kernel)
    assert "conv" not in levels and levels[-1] == "oracle"


def _ladder(art):
    import jax

    def build(name):
        return jax.jit(lambda xw: compiler.run_compiled(
            art, xw, engine=name, interpret=True).argmax(-1))

    return ops.EngineLadder([(n, (lambda n=n: build(n)))
                             for n in ops.engine_levels(art, use_kernel=True)])


@pytest.mark.parametrize("fault", [None, "kernel.conv"])
def test_served_end_to_end_through_gateway(fault):
    """Requests through Gateway -> runner -> EngineLadder -> the conv
    kernel (interpret mode); a kernel fault demotes to the oracle rung and
    every answer still matches the reference."""
    from repro.launch.serve import make_runner
    from repro.runtime.gateway import Gateway

    img, words, include, weights = make_case(2, n=40)
    art = compiler.compile_tm(CFG, ta_of(include), weights=weights)
    ladder = _ladder(art)
    run_rows = make_runner(ladder, 16, words.shape[1], words.dtype)

    async def serve():
        gw = await Gateway(lambda tenant, rows, quality=0: run_rows(rows),
                           bucket=16, max_wait=0.005).start()
        answers = await asyncio.gather(*[gw.offer("t0", w) for w in words])
        await gw.drain()
        return answers

    if fault:
        with faults.injected(fault):
            answers = asyncio.run(serve())
    else:
        answers = asyncio.run(serve())
    assert all(r.ok for r in answers)
    want = ref_class_sums(img, include, weights).argmax(-1)
    np.testing.assert_array_equal([r.pred for r in answers], want)
    assert ladder.engine == ("oracle" if fault else "conv")
    assert [d["to"] for d in ladder.demotions] == (["oracle"] if fault
                                                    else [])


@pytest.mark.parametrize("extra", [[], ["--zoo", "2"]], ids=["one", "zoo"])
def test_serve_launcher_serves_convcotm(extra):
    """``launch/serve.py --arch convcotm-mnist`` at the published sizes
    runs the normal path: seeded bank, compile, ladder, gateway, and with
    ``--zoo`` the artifact zoo between them."""
    from repro.launch import serve

    args = serve.build_parser().parse_args(
        ["--arch", "convcotm-mnist", "--requests", "96", "--bucket", "32",
         "--n-train", "64"] + extra)
    out = serve.serve_tm(args)
    assert out["gateway"]["answered"] == 96
    assert ("zoo" in out["gateway"]) == bool(extra)
    uk, _ = ops.kernel_dispatch()
    assert out["serve"]["ladder"] == (["conv"] if uk else []) + ["oracle"]
    assert out["serve"]["demotions"] == []
    assert (out["preds"] >= 0).all()


def _artifact_of(kind: str):
    """A compiled artifact with tm-mnist's and convcotm-mnist's F = 784
    and K = 10: vanilla, convolutional at the published geometry, or
    convolutional with a 9x9 window."""
    import jax

    if kind == "vanilla":
        cfg = tm.TMConfig(n_features=784, n_classes=10, clauses_per_class=2)
        return compiler.compile_tm(cfg, tm.init(cfg, jax.random.PRNGKey(0))
                                   .ta_state)
    cfg = tm.ConvTMConfig(image_h=28, image_w=28,
                          window=10 if kind == "conv" else 9,
                          n_clauses=16, n_classes=10)
    rng = np.random.default_rng(0)
    include = rng.random((16, cfg.geometry.literals)) < 0.02
    weights = rng.integers(-127, 128, (16, 10)).astype(np.int32)
    return compiler.compile_tm(cfg, ta_of(include), weights=weights)


@pytest.mark.parametrize("arch, kind", [("tm-mnist", "conv"),
                                        ("convcotm-mnist", "vanilla"),
                                        ("convcotm-mnist", "conv-window9")])
def test_serve_refuses_an_artifact_of_another_kind(arch, kind, tmp_path):
    """F and K agree in every case; the artifact's kind or geometry does
    not, and serving it would feed the ladder rows it cannot read."""
    from repro.launch import serve

    path = _artifact_of(kind).save(str(tmp_path / "art.npz"))
    args = serve.build_parser().parse_args(
        ["--arch", arch, "--requests", "32", "--bucket", "32",
         "--artifact", path])
    with pytest.raises(SystemExit, match="geometry"):
        serve.serve_tm(args)


@pytest.mark.parametrize("arch, kind", [("tm-mnist", "vanilla"),
                                        ("convcotm-mnist", "conv")])
def test_serve_loads_an_artifact_of_its_kind(arch, kind, tmp_path):
    from repro.launch import serve

    path = _artifact_of(kind).save(str(tmp_path / "art.npz"))
    args = serve.build_parser().parse_args(
        ["--arch", arch, "--requests", "32", "--bucket", "32",
         "--artifact", path])
    assert serve.serve_tm(args)["gateway"]["answered"] == 32


def test_runner_spans_cover_the_conv_runner(tmp_path):
    """The serve loop's runner spans (``repro.runner.*``) time each call
    on the conv ladder as they do on the vanilla one: one of each phase."""
    import glob

    import jax

    from repro.launch.serve import make_runner

    _, words, include, weights = make_case(0, n=20)
    art = compiler.compile_tm(CFG, ta_of(include), weights=weights)
    run_rows = make_runner(_ladder(art), 16, words.shape[1], words.dtype)
    run_rows(words[:16])                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    run_rows(words[:16])
    run_rows(words[16:])
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    names = [ev.name for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("repro.runner.")]
    for phase in ("pad", "copy_in", "dispatch", "wait", "copy_out"):
        assert names.count(f"repro.runner.{phase}") == 2, (phase, names)
