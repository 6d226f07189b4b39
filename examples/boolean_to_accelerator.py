"""The full MATADOR flow (paper Fig. 6): train -> boolean-to-silicon compile
-> auto-verify -> deploy artifact -> throughput report.

    PYTHONPATH=src python examples/boolean_to_accelerator.py
"""

import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compiler, packetizer, tm, train
from repro.data import paper_dataset


def main() -> None:
    # 1. train (the GUI's "Train" stage)
    X, y, Xte, yte = paper_dataset("mnist", n_train=3000, n_test=1000)
    config = tm.TMConfig(n_features=784, n_classes=10, clauses_per_class=40,
                         threshold=40, s=8.0)
    state = tm.init(config, jax.random.PRNGKey(0))
    state = train.fit(config, state, jnp.asarray(X), jnp.asarray(y),
                      epochs=6, batch_size=50, rng=jax.random.PRNGKey(1))

    # 2. boolean-to-silicon: compile the automata into the compact datapath
    compiled = compiler.compile_tm(config, state.ta_state)
    s = compiled.stats
    print("== design generation report (paper Fig. 8 analog) ==")
    print(f"  include sparsity     : {s.include_sparsity:.2%}")
    print(f"  clauses dense->unique: {s.n_clauses_dense} -> {s.n_clauses_unique} "
          f"(sharing {s.clause_sharing:.2%})")
    print(f"  words dense->active  : {s.n_words_dense} -> {s.n_words_active} "
          f"(compaction {s.word_compaction:.2%})")
    print(f"  partial AND terms    : {s.n_partial_terms_dense} -> "
          f"{s.n_partial_terms_unique} (sub-clause sharing "
          f"{s.partial_term_sharing:.2%})")

    # 3. design verification (the auto-debug stage): compiled == dense model
    pred_dense = np.asarray(tm.predict(config, state, jnp.asarray(Xte)))
    pred_comp = np.asarray(compiler.predict_compiled(compiled, jnp.asarray(Xte)))
    assert (pred_dense == pred_comp).all(), "verification FAILED"
    print("verification: compiled artifact == dense model on 1000 samples OK")

    # 3b. the same datapath through the fused Pallas kernel (compiled on a
    # TPU, interpret mode elsewhere)
    pred_kernel = np.asarray(
        compiler.predict_compiled(compiled, jnp.asarray(Xte[:64]),
                                  engine="dense"))
    assert (pred_kernel == pred_dense[:64]).all()
    print("verification: fused Pallas inference kernel path OK")

    # 4. deployment artifact
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "matador_accelerator.npz")
        compiled.save(path)
        size = os.path.getsize(path)
        reloaded = compiler.CompiledTM.load(path)
    print(f"deploy artifact: {size / 1024:.1f} KiB (fits on-chip — the paper's "
          "'no BRAM' point)")

    # 5. throughput (the jupyter-notebook stage)
    xp = packetizer.pack_literals(jnp.asarray(Xte))
    run = jax.jit(lambda xw: jnp.argmax(compiler.run_compiled(reloaded, xw), -1))
    run(xp).block_until_ready()
    t0 = time.perf_counter()
    out = run(xp)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    acc = float((np.asarray(out) == yte).mean())
    print(f"throughput: {len(yte) / dt:,.0f} inf/s "
          f"({dt / len(yte) * 1e6:.2f} us/inference), accuracy {acc:.3f}")


if __name__ == "__main__":
    main()
