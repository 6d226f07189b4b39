"""Fault-tolerance drills: every recovery behavior the runtime claims is
exercised by arming a fault site (runtime/faults.py) and asserting the
system degrades the way it promises.

  * artifact integrity — bit-flips, stale schemas, truncation, tampered
    schedules and checksum mismatches are REJECTED at load; an aborted
    save never clobbers the previous artifact;
  * serve degradation ladder — injected kernel failures demote
    factorized -> sparse -> dense -> oracle and the stream completes;
    slow buckets trip the ``--bucket-deadline`` demotion;
  * preemption-safe training — SIGTERM mid-run exits with
    RESUME_EXIT_CODE, restarts resume from the checkpoint, and the final
    model is bit-identical to an uninterrupted run (deterministic
    hash-RNG training + consumed-position loader state);
  * checkpoint substrate — async write failures surface instead of being
    swallowed; stale ``step_*.tmp`` debris is cleaned; malformed entries
    never crash ``latest_step``/gc.

The module is marked ``faults`` so CI's drill job selects it with
``-m faults``; the tests also run (unmarked selection) in tier-1.
"""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.core import compiler, tm, train
from repro.data import ShardedBatcher, make_boolean_classification
from repro.kernels import ops
from repro.runtime import RESUME_EXIT_CODE, faults

pytestmark = pytest.mark.faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu")
ENV.pop("REPRO_FAULT_INJECT", None)


def _run(code_or_argv, env_extra=None, timeout=600):
    env = dict(ENV, **(env_extra or {}))
    argv = ([sys.executable, "-c", code_or_argv]
            if isinstance(code_or_argv, str) else
            [sys.executable] + code_or_argv)
    return subprocess.run(argv, env=env, capture_output=True, text=True,
                          timeout=timeout)


# --------------------------------------------------------------------------
# kill / resume (the original end-to-end drill, explicit step loop)
# --------------------------------------------------------------------------

def _train(steps, ckpt_dir, out_npy):
    code = f"""
import jax, jax.numpy as jnp, numpy as np
from repro.checkpoint import CheckpointManager
from repro.configs.matador_tm import TM_CONFIGS
from repro.core import tm
from repro.data import ShardedBatcher, make_boolean_classification
from repro.kernels import ops

config = tm.TMConfig(n_features=32, n_classes=3, clauses_per_class=8)
X, y = make_boolean_classification(512, 32, 3, seed=0)
mgr = CheckpointManager({ckpt_dir!r}, max_to_keep=2)
state = tm.init(config, jax.random.PRNGKey(0))
ta = state.ta_state
loader = ShardedBatcher((X, y), 32, seed=1, prefetch=0)
start = 0
if mgr.latest_step() is not None:
    restored, extra = mgr.restore({{"ta": np.asarray(ta)}})
    ta = jnp.asarray(restored["ta"])
    loader.load_state_dict(extra["loader"])
    start = extra["step"]
it = iter(loader)
for step in range(start, {steps}):
    xb, yb = next(it)
    ta, _ = ops.tm_train_step_kernel(config, ta, jnp.asarray(xb), jnp.asarray(yb), jnp.uint32(step))
    mgr.save(step + 1, {{"ta": np.asarray(ta)}},
             extra={{"step": step + 1, "loader": loader.state_dict()}})
mgr.wait()
np.save({out_npy!r}, np.asarray(ta))
"""
    r = _run(code)
    assert r.returncode == 0, r.stdout + r.stderr


def test_kill_and_resume_is_bit_identical():
    with tempfile.TemporaryDirectory() as d:
        ref = os.path.join(d, "ref.npy")
        _train(12, os.path.join(d, "ckpt_ref"), ref)

        ck = os.path.join(d, "ckpt_resume")
        part = os.path.join(d, "part.npy")
        _train(7, ck, part)              # "preempted" after step 7
        fin = os.path.join(d, "fin.npy")
        _train(12, ck, fin)              # restart resumes from step 7

        np.testing.assert_array_equal(np.load(ref), np.load(fin))


def test_resume_skips_completed_steps():
    with tempfile.TemporaryDirectory() as d:
        ck = os.path.join(d, "ckpt")
        out = os.path.join(d, "a.npy")
        _train(5, ck, out)
        steps = sorted(os.listdir(ck))
        assert steps[-1] == "step_0000000005"


# --------------------------------------------------------------------------
# fault-injection harness itself
# --------------------------------------------------------------------------

def test_fault_spec_grammar():
    specs = faults.parse_spec(
        "train.sigterm@7, serve.slow_bucket@3:0.5, kernel.dense*2")
    assert [s.site for s in specs] == [
        "train.sigterm", "serve.slow_bucket", "kernel.dense"]
    assert specs[0].step == 7 and specs[0].param is None
    assert specs[1].step == 3 and specs[1].param == 0.5
    assert specs[2].count == 2
    with pytest.raises(ValueError, match="unknown fault site"):
        faults.parse_spec("no.such.site")


def test_fault_injector_count_and_step_gating():
    inj = faults.FaultInjector(faults.parse_spec("kernel.dense*2"))
    assert inj.poll("kernel.dense") is not None
    assert inj.poll("kernel.dense") is not None
    assert inj.poll("kernel.dense") is None          # count exhausted
    inj = faults.FaultInjector(faults.parse_spec("train.sigterm@7"))
    assert inj.poll("train.sigterm", step=6) is None
    assert inj.poll("train.sigterm") is None         # no step at call site
    assert inj.poll("train.sigterm", step=7) is not None


def test_injected_context_scopes_arming():
    assert not faults.armed()
    with faults.injected("kernel.dense"):
        assert faults.armed()
        with pytest.raises(faults.InjectedFault):
            faults.raise_if("kernel.dense")
    assert not faults.armed()
    faults.raise_if("kernel.dense")                  # disarmed: no-op


# --------------------------------------------------------------------------
# artifact integrity
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_compiled():
    config = tm.TMConfig(n_features=32, n_classes=3, clauses_per_class=8)
    X, y = make_boolean_classification(256, 32, 3, seed=0)
    state = tm.init(config, jax.random.PRNGKey(0))
    state = train.fit(config, state, jnp.asarray(X), jnp.asarray(y),
                      epochs=1, batch_size=32, rng=jax.random.PRNGKey(1))
    return config, compiler.compile_tm(config, state.ta_state)


def _rewrite(path, mutate, fix_checksum=True):
    """Re-write an artifact with a mutation; optionally re-sign it so the
    mutation exercises the layer BEHIND the checksum (validate_artifact)."""
    z = np.load(path)
    meta = json.loads(bytes(z["meta"]).decode())
    arrays = {k: np.array(z[k]) for k in z.files if k != "meta"}
    mutate(arrays, meta)
    if fix_checksum:
        meta.pop("checksum", None)
        meta["checksum"] = compiler._artifact_checksum(arrays, meta)
    with open(path, "wb") as f:
        np.savez_compressed(
            f, meta=np.frombuffer(json.dumps(meta).encode(), np.uint8),
            **arrays)


def test_artifact_roundtrip_is_verified(tiny_compiled, tmp_path):
    _, compiled = tiny_compiled
    path = compiled.save(str(tmp_path / "art.npz"))
    again = compiler.CompiledTM.load(path)
    np.testing.assert_array_equal(again.votes, compiled.votes)
    np.testing.assert_array_equal(again.include_words, compiled.include_words)


def test_artifact_bitflip_rejected(tiny_compiled, tmp_path):
    _, compiled = tiny_compiled
    with faults.injected("artifact.bitflip"):
        path = compiled.save(str(tmp_path / "art.npz"))
    with pytest.raises(compiler.ArtifactError):
        compiler.CompiledTM.load(path)


def test_artifact_stale_schema_rejected(tiny_compiled, tmp_path):
    _, compiled = tiny_compiled
    path = compiled.save(str(tmp_path / "art.npz"))
    _rewrite(path, lambda arrays, meta: meta.update(schema=0))
    with pytest.raises(compiler.ArtifactError, match="schema version 0"):
        compiler.CompiledTM.load(path)


def test_artifact_checksum_mismatch_rejected(tiny_compiled, tmp_path):
    _, compiled = tiny_compiled
    path = compiled.save(str(tmp_path / "art.npz"))

    def flip_votes(arrays, meta):
        arrays["votes"] = arrays["votes"] + 1

    _rewrite(path, flip_votes, fix_checksum=False)
    with pytest.raises(compiler.ArtifactError, match="checksum"):
        compiler.CompiledTM.load(path)


def test_artifact_truncated_rejected(tiny_compiled, tmp_path):
    _, compiled = tiny_compiled
    path = compiled.save(str(tmp_path / "art.npz"))
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[: len(blob) // 2])
    with pytest.raises(compiler.ArtifactError, match="unreadable"):
        compiler.CompiledTM.load(path)


def test_artifact_tampered_schedule_rejected(tiny_compiled, tmp_path):
    # a correctly-signed artifact with OUT-OF-RANGE chain ids (a buggy or
    # adversarial producer) must fail structural validation — those ids
    # would gather-clamp into silently wrong class sums
    _, compiled = tiny_compiled
    path = compiled.save(str(tmp_path / "art.npz"))

    def poison(arrays, meta):
        bad = np.array(arrays["sched_chain_ids"])
        bad[0, 0] = meta["schedule"]["n_lit_bits"] + 7
        arrays["sched_chain_ids"] = bad

    _rewrite(path, poison, fix_checksum=True)
    with pytest.raises(compiler.ArtifactError):
        compiler.CompiledTM.load(path)


def test_artifact_unsorted_word_ids_rejected(tiny_compiled, tmp_path):
    _, compiled = tiny_compiled
    if compiled.word_ids.shape[0] < 2:
        pytest.skip("needs >=2 active words")
    path = compiled.save(str(tmp_path / "art.npz"))

    def unsort(arrays, meta):
        arrays["word_ids"] = np.ascontiguousarray(arrays["word_ids"][::-1])

    _rewrite(path, unsort, fix_checksum=True)
    with pytest.raises(compiler.ArtifactError):
        compiler.CompiledTM.load(path)


def test_artifact_save_abort_preserves_previous(tiny_compiled, tmp_path):
    _, compiled = tiny_compiled
    path = compiled.save(str(tmp_path / "art.npz"))
    before = open(path, "rb").read()
    compiled.record_tuned("sparse_infer", 128, {"block_c": 8}, rows=1,
                          mode="drill")
    with faults.injected("artifact.save_abort"):
        with pytest.raises(faults.InjectedFault):
            compiled.save(path)
    # the aborted save left no tmp debris and did not touch the artifact
    assert [p for p in os.listdir(tmp_path) if ".tmp" in p] == []
    assert open(path, "rb").read() == before
    compiler.CompiledTM.load(path)                   # still serves


# --------------------------------------------------------------------------
# checkpoint substrate
# --------------------------------------------------------------------------

def test_ckpt_async_write_failure_surfaces(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"))
    with faults.injected("ckpt.write_fail"):
        mgr.save(1, {"a": np.arange(3)}, blocking=False)
        with pytest.raises(faults.InjectedFault):
            mgr.wait()                               # not swallowed
    # the failure is consumed: the manager keeps working afterwards
    mgr.save(2, {"a": np.arange(3)})
    assert mgr.latest_step() == 2


def test_ckpt_blocking_write_failure_raises_inline(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"))
    with faults.injected("ckpt.write_fail"):
        with pytest.raises(faults.InjectedFault):
            mgr.save(1, {"a": np.arange(3)}, blocking=True)


def test_ckpt_stale_tmp_cleanup_and_malformed_names(tmp_path):
    d = tmp_path / "ck"
    mgr = CheckpointManager(str(d))
    mgr.save(5, {"a": np.arange(3)}, extra={"step": 5})
    # a writer killed mid-save + a stray entry sharing the prefix
    os.makedirs(d / "step_0000000009.tmp")
    (d / "step_0000000009.tmp" / "arrays.npz").write_bytes(b"partial")
    os.makedirs(d / "step_bogus")
    mgr2 = CheckpointManager(str(d))
    assert not (d / "step_0000000009.tmp").exists()  # debris removed
    assert mgr2.latest_step() == 5                   # bogus entry ignored
    for s in (6, 7, 8, 9):
        mgr2.save(s, {"a": np.arange(3)})            # _gc tolerates step_bogus
    assert mgr2.latest_step() == 9


def test_loader_state_dict_is_consumed_position():
    X, y = make_boolean_classification(200, 16, 2, seed=0)
    a = ShardedBatcher((X, y), 10, seed=3, prefetch=2)
    it = iter(a)
    got = [next(it) for _ in range(3)]
    # the prefetch worker runs ahead, but the checkpointable state must be
    # the position the TRAINING LOOP consumed, not the worker's cursor
    st = a.state_dict()
    assert st["step_in_epoch"] == 3
    b = ShardedBatcher((X, y), 10, seed=3, prefetch=0)
    b.load_state_dict(st)
    ref = ShardedBatcher((X, y), 10, seed=3, prefetch=0)
    rit = iter(ref)
    for _ in range(3):
        next(rit)
    np.testing.assert_array_equal(next(iter(b))[0], next(rit)[0])
    del it, got


# --------------------------------------------------------------------------
# engine degradation ladder
# --------------------------------------------------------------------------

def test_engine_ladder_demotes_and_counts():
    def bad_builder():
        def f(x):
            raise RuntimeError("boom")
        return f

    def good_builder():
        return lambda x: x + 1

    lad = ops.EngineLadder([("bad", bad_builder), ("good", good_builder)])
    out = lad.run(lambda: np.int64(1), bucket=0)
    assert out == 2 and lad.engine == "good"
    assert lad.counts == {"bad": 0, "good": 1}
    assert lad.demotions[0]["frm"] == "bad" and lad.demotions[0]["to"] == "good"
    assert lad.exhausted


def test_engine_ladder_exhausted_propagates():
    def bad_builder():
        def f(x):
            raise RuntimeError("boom")
        return f

    lad = ops.EngineLadder([("only", bad_builder)])
    with pytest.raises(RuntimeError, match="boom"):
        lad.run(lambda: np.int64(1))
    assert not lad.demote("manual")                  # nowhere to go


def test_engine_ladder_repromotes_after_healthy_streak():
    """A transient failure demotes; after ``promote_after`` healthy buckets
    the ladder probes one level up and promotes when the probe succeeds —
    the probe bucket itself is served by the higher engine."""
    calls = {"n": 0}

    def flaky_builder():
        def f(x):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("transient")
            return x + 10
        return f

    lad = ops.EngineLadder(
        [("flaky", flaky_builder), ("good", lambda: (lambda x: x + 1))],
        promote_after=2)
    assert lad.run(lambda: np.int64(0), bucket=0) == 1   # demoted to good
    assert lad.engine == "good" and len(lad.demotions) == 1
    assert lad.run(lambda: np.int64(0), bucket=1) == 1   # healthy streak 2
    out = lad.run(lambda: np.int64(0), bucket=2)         # probe bucket
    assert out == 10 and lad.engine == "flaky"
    assert lad.promotions == [dict(to="flaky", frm="good", bucket=2,
                                   after_healthy=2)]
    assert lad.counts == {"flaky": 1, "good": 2}
    assert lad.probe_failures == []


def test_engine_ladder_failed_probe_doubles_cooldown_drill():
    """Fault-injection drill: a kernel engine that keeps faulting makes
    every probe fail — each failed probe falls back to the serving engine
    for the SAME bucket and doubles the healthy-streak cooldown, so the
    fault converges to exponentially-rare probes; once the fault clears,
    the next probe promotes."""
    def kernel_builder():
        def f(x):
            faults.raise_if("kernel.dense")
            return x + 10
        return f

    lad = ops.EngineLadder(
        [("kernel", kernel_builder), ("oracle", lambda: (lambda x: x + 1))],
        promote_after=1)
    with faults.injected("kernel.dense*3"):
        # firing 1: initial demotion; firings 2-3: two failed probes
        assert lad.run(lambda: np.int64(0), bucket=0) == 1   # demote; streak 1
        assert lad.engine == "oracle"
        assert lad.run(lambda: np.int64(0), bucket=1) == 1   # probe fails
        assert len(lad.probe_failures) == 1 and lad._cooldown == 2
        assert lad.run(lambda: np.int64(0), bucket=2) == 1   # streak 2
        assert lad.run(lambda: np.int64(0), bucket=3) == 1   # probe fails
        assert len(lad.probe_failures) == 2 and lad._cooldown == 4
        for b in range(4, 7):                                # streak 2..4
            assert lad.run(lambda: np.int64(0), bucket=b) == 1
        # fault site exhausted: this probe succeeds and promotes
        assert lad.run(lambda: np.int64(0), bucket=7) == 10
    assert lad.engine == "kernel"
    assert lad.promotions[0]["to"] == "kernel"
    # every bucket was answered by SOME engine — probes never drop work
    assert lad.counts["kernel"] + lad.counts["oracle"] == 8


SERVE_ARGV = ["--arch", "tm-tiny", "--requests", "640", "--bucket", "128",
              "--epochs", "1", "--n-train", "256"]


def _serve_full_ladder(argv, env_extra):
    """Run the serve launcher with the factorize threshold at 0, so the
    ladder is the full ``[factorized, sparse, dense, oracle]`` whatever
    sharing tm-tiny's trained bank measures, and every kernel fault site
    is reached by the drills below."""
    code = ("import sys\n"
            "from repro.core import compiler\n"
            "compiler.FACTORIZE_SHARING_THRESHOLD = 0.0\n"
            "from repro.launch import serve\n"
            f"sys.argv = ['serve'] + {argv!r}\n"
            "serve.main()\n")
    return _run(code, env_extra=env_extra)


def _serve_health(r):
    assert r.returncode == 0, r.stdout + r.stderr
    lines = [l for l in r.stdout.splitlines() if l.startswith("SERVE_HEALTH ")]
    assert lines, r.stdout + r.stderr
    return json.loads(lines[0][len("SERVE_HEALTH "):])


@pytest.mark.parametrize("use_kernel, shares, want", [
    (True, True, ["factorized", "sparse", "dense", "oracle"]),
    (True, False, ["sparse", "dense", "oracle"]),
    (False, True, ["oracle"]),
    (False, False, ["oracle"]),
])
def test_engine_levels_honour_the_pins(tiny_compiled, monkeypatch,
                                       use_kernel, shares, want):
    """The ladder is pinned by the platform and the artifact's measured
    sharing against the compiler's threshold, and by nothing else."""
    _, compiled = tiny_compiled
    monkeypatch.setattr(compiler, "FACTORIZE_SHARING_THRESHOLD",
                        0.0 if shares else 1.01)
    assert ops.engine_levels(compiled, use_kernel) == want


def test_engine_levels_follow_the_measured_sharing(tiny_compiled):
    _, compiled = tiny_compiled
    shares = (compiled.stats.partial_term_sharing
              >= compiler.FACTORIZE_SHARING_THRESHOLD)
    assert ops.engine_levels(compiled, True) == (
        ["factorized"] if shares else []) + ["sparse", "dense", "oracle"]


def test_serve_ladder_demotes_to_oracle_under_kernel_faults():
    r = _serve_full_ladder(SERVE_ARGV, env_extra={
        "REPRO_USE_PALLAS": "1",
        "REPRO_FAULT_INJECT": "kernel.factorized,kernel.sparse,kernel.dense",
    })
    h = _serve_health(r)
    assert h["ladder"] == ["factorized", "sparse", "dense", "oracle"]
    assert h["final_engine"] == "oracle"
    assert [d["frm"] for d in h["demotions"]] == [
        "factorized", "sparse", "dense"]
    # every bucket was still served — the run degraded, it did not drop
    assert h["engine_buckets"]["oracle"] == h["buckets"]


def test_serve_healthy_kernel_path_stays_on_top_engine():
    r = _serve_full_ladder(SERVE_ARGV, env_extra={"REPRO_USE_PALLAS": "1"})
    h = _serve_health(r)
    assert h["final_engine"] == "factorized" and h["demotions"] == []
    assert h["engine_buckets"]["factorized"] == h["buckets"]


def test_serve_bucket_deadline_demotes_on_slow_bucket():
    r = _serve_full_ladder(SERVE_ARGV + ["--bucket-deadline", "3"],
                           env_extra={
                               "REPRO_USE_PALLAS": "1",
                               "REPRO_FAULT_INJECT": "serve.slow_bucket@3:0.3",
                           })
    h = _serve_health(r)
    assert h["stragglers"] and h["stragglers"][0]["step"] == 3
    assert h["demotions"] and "deadline" in h["demotions"][0]["reason"]
    assert h["demotions"][0]["frm"] == "factorized"


def test_serve_refuses_corrupt_artifact(tiny_compiled, tmp_path):
    _, compiled = tiny_compiled
    with faults.injected("artifact.bitflip"):
        path = compiled.save(str(tmp_path / "art.npz"))
    r = _run(["-m", "repro.launch.serve", "--arch", "tm-tiny",
              "--requests", "128", "--bucket", "128", "--artifact", path])
    assert r.returncode != 0
    assert "refusing to serve" in (r.stdout + r.stderr)


# --------------------------------------------------------------------------
# preemption-safe training (SIGTERM -> RESUME_EXIT_CODE -> bit-exact resume)
# --------------------------------------------------------------------------

def _fit_code(ckpt, out):
    return f"""
import jax, jax.numpy as jnp, numpy as np
from repro.checkpoint import CheckpointManager
from repro.core import tm, train
from repro.data import make_boolean_classification
from repro.runtime import PreemptionHandler, StragglerMonitor

config = tm.TMConfig(n_features=32, n_classes=3, clauses_per_class=8)
X, y = make_boolean_classification(256, 32, 3, seed=0)
state = tm.init(config, jax.random.PRNGKey(0))
state = train.fit(config, state, jnp.asarray(X), jnp.asarray(y),
                  epochs=3, batch_size=32, rng=jax.random.PRNGKey(1),
                  engine="kernel", ckpt_manager=CheckpointManager({ckpt!r}),
                  ckpt_every=2, preemption=PreemptionHandler().install(),
                  monitor=StragglerMonitor())
np.save({out!r}, np.asarray(state.ta_state))
"""


def test_fit_sigterm_exits_resume_code_and_resumes_bit_exact():
    with tempfile.TemporaryDirectory() as d:
        ref = os.path.join(d, "ref.npy")
        r = _run(_fit_code(os.path.join(d, "ck_ref"), ref))
        assert r.returncode == 0, r.stdout + r.stderr

        ck = os.path.join(d, "ck")
        out = os.path.join(d, "out.npy")
        # SIGTERM mid-epoch-1 (global step 10 of 24): the handler must
        # checkpoint and exit with the restart-me code, not crash
        r = _run(_fit_code(ck, out),
                 env_extra={"REPRO_FAULT_INJECT": "train.sigterm@9"})
        assert r.returncode == RESUME_EXIT_CODE, r.stdout + r.stderr
        assert not os.path.exists(out)

        r = _run(_fit_code(ck, out))
        assert r.returncode == 0, r.stdout + r.stderr
        assert "fit: resumed" in r.stdout
        np.testing.assert_array_equal(np.load(ref), np.load(out))


def test_launch_train_sigterm_resume_with_prefetch_loader():
    argv = ["-m", "repro.launch.train", "--arch", "tm-tiny",
            "--steps", "12", "--batch-size", "32", "--n-train", "256",
            "--ckpt-every", "3", "--log-every", "100"]
    with tempfile.TemporaryDirectory() as d:
        ck_ref = os.path.join(d, "ck_ref")
        r = _run(argv + ["--ckpt-dir", ck_ref])
        assert r.returncode == 0, r.stdout + r.stderr

        ck = os.path.join(d, "ck")
        r = _run(argv + ["--ckpt-dir", ck],
                 env_extra={"REPRO_FAULT_INJECT": "train.sigterm@5"})
        assert r.returncode == RESUME_EXIT_CODE, r.stdout + r.stderr
        r = _run(argv + ["--ckpt-dir", ck])
        assert r.returncode == 0, r.stdout + r.stderr
        assert "resumed from step 6" in r.stdout

        a = np.load(os.path.join(ck_ref, "step_0000000012", "arrays.npz"))
        b = np.load(os.path.join(ck, "step_0000000012", "arrays.npz"))
        np.testing.assert_array_equal(a["ta"], b["ta"])
