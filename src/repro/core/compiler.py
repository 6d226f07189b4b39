"""The boolean-to-silicon pass — MATADOR's model compiler, TPU edition.

The paper translates a trained TM into a compact combinational circuit by
exploiting (a) include sparsity and (b) logic sharing between clauses within
and across classes (paper §II, Fig. 3, Fig. 8).  On FPGA that compression is
performed by the synthesis tool's logic-absorption algorithms; here it is an
explicit, host-side (numpy) compilation pass with three optimizations:

  1. **Empty-clause removal** — all-exclude clauses are constant 0 at
     inference; drop them (paper: they never reach the netlist).
  2. **Clause deduplication** — identical include rows are evaluated once;
     their votes are folded into an int32 (unique_clause x class) vote
     matrix carrying multiplicity x polarity.  This is clause-granular logic
     sharing: the shared sub-circuit is computed once and fanned out.
  3. **Dead-word elimination** — packed literal words that no surviving
     clause includes are never loaded (column pruning).  This is the
     bandwidth optimization: the accelerator only streams words that matter.
  4. **Chain-schedule emission** — unique clauses are clustered by
     (chain length, active-word signature) and each clause's include bits
     become a compacted literal-id chain, tiled into a CSR-like
     block-sparse execution schedule (``kernels/sparse_infer.py``).  The
     sparse fused kernel walks only the tiles that exist, so inference
     work scales with the artifact's include count — the paper's
     "miniscule number of AND gates" — instead of ``C x W``.
  5. **Shared-term factorization** — the unique (word, include-pattern)
     AND terms across the deduped bank are extracted into a term table and
     each clause is rewritten as a chain of TERM ids
     (``kernels/term_infer.py``).  This is sub-clause logic sharing (paper
     Fig. 5 absorption, the opportunity ``partial_term_sharing``
     measures): a term shared by ``n`` clauses is evaluated once per
     sample slab instead of ``n`` times.  The factorized kernel is the
     kernel-path default when the artifact's measured sharing clears
     ``FACTORIZE_SHARING_THRESHOLD``.

The compiled artifact runs through the same bitpacked evaluation path (and
Pallas kernels) as the dense model and is *provably equivalent* to dense
inference (tests/test_compiler.py + tests/test_sparse_infer.py, hypothesis
properties).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import struct
import zipfile
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core import packetizer, tm
from repro.kernels.conv_infer import Geometry
from repro.runtime import faults

# kernel-path default: serve the factorized (two-level) schedule when at
# least this fraction of the artifact's per-word AND terms are absorbed by
# sub-clause sharing — below it the term table amortizes too little stage-1
# work to beat the flat bit-chain kernel
FACTORIZE_SHARING_THRESHOLD = 0.30

# On-disk artifact schema.  Version 1 added the integrity envelope (schema
# tag + content checksum, saved atomically); version-0 artifacts (no tag)
# predate it and are REJECTED at load — an unverifiable artifact must be
# recompiled, not served on trust.
ARTIFACT_SCHEMA_VERSION = 2   # v2: per-tile-prefix anytime margin metadata


class ArtifactError(RuntimeError):
    """A compiled artifact failed integrity verification at load.

    Raised for unreadable/truncated files, schema-version mismatches,
    content-checksum mismatches (bit-rot, partial writes), and schedule
    invariant violations.  The serve path treats this as fatal: a corrupt
    artifact must never serve silently-wrong predictions (out-of-range
    word gathers clamp instead of failing).
    """


def _artifact_checksum(arrays: dict, meta: dict) -> str:
    """Content hash over every artifact array + the meta (sans checksum).

    Arrays hash (name, dtype, shape, bytes) in sorted-name order; the meta
    dict hashes as canonical JSON, so save() and load() agree byte-for-byte
    on the same content.
    """
    h = hashlib.sha256()
    for key in sorted(arrays):
        a = np.ascontiguousarray(arrays[key])
        h.update(key.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    h.update(json.dumps(meta, sort_keys=True).encode())
    return h.hexdigest()


def _payload_offset(path: str) -> Optional[int]:
    """Byte offset of real member payload inside the saved npz.

    The bit-rot drill (``artifact.bitflip``) flips one byte of the file;
    aiming at the middle of the *largest member's compressed data* keeps
    the drill meaningful regardless of how the zip layout shifts between
    schema versions — a flip at a naive ``size // 2`` can land in a local
    file header's redundant csize/crc fields, which zipfile never reads
    (it trusts the central directory), so the "corrupt" artifact would
    load cleanly and the drill would assert nothing.
    """
    try:
        with zipfile.ZipFile(path) as zf:
            info = max(zf.infolist(), key=lambda zi: zi.compress_size)
            with open(path, "rb") as f:
                # local header: fnlen @ +26, extralen @ +28 (little-endian)
                f.seek(info.header_offset + 26)
                fnlen, extralen = struct.unpack("<HH", f.read(4))
            data_start = info.header_offset + 30 + fnlen + extralen
            return data_start + info.compress_size // 2
    except Exception:
        return None


@dataclasses.dataclass
class CompileStats:
    n_clauses_dense: int
    n_clauses_nonempty: int
    n_clauses_unique: int
    n_words_dense: int
    n_words_active: int
    n_includes: int
    n_literals: int
    # partial-clause (HCB-term) sharing: two clauses whose include bits agree
    # within word w share that word's AND gate (paper Fig. 5 logic sharing —
    # on FPGA the synthesis absorbs these; we quantify the opportunity)
    n_partial_terms_dense: int = 0
    n_partial_terms_unique: int = 0
    # convolutional artifacts: patch positions, literals per patch, and the
    # range of the served (clause, class) weights after dedup
    n_positions: int = 0
    n_patch_literals: int = 0
    weight_min: int = 0
    weight_max: int = 0

    @property
    def include_sparsity(self) -> float:
        tot = self.n_clauses_dense * self.n_literals
        return 1.0 - self.n_includes / max(tot, 1)

    @property
    def clause_sharing(self) -> float:
        """Fraction of non-empty clauses absorbed by sharing (paper Fig. 8)."""
        if self.n_clauses_nonempty == 0:
            return 0.0
        return 1.0 - self.n_clauses_unique / self.n_clauses_nonempty

    @property
    def word_compaction(self) -> float:
        return 1.0 - self.n_words_active / max(self.n_words_dense, 1)

    @property
    def partial_term_sharing(self) -> float:
        """Fraction of per-word AND gates absorbed by sub-clause sharing."""
        if self.n_partial_terms_dense == 0:
            return 0.0
        return 1.0 - self.n_partial_terms_unique / self.n_partial_terms_dense

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(
            include_sparsity=self.include_sparsity,
            clause_sharing=self.clause_sharing,
            word_compaction=self.word_compaction,
            partial_term_sharing=self.partial_term_sharing,
        )
        return d


@dataclasses.dataclass
class DriftStats:
    """How far a live automata bank has drifted from a reference bank.

    Measured on the DENSE packed include words (every raw clause, before
    dedup/pruning), so the comparison is stable across recompiles: two
    banks compare row-for-row regardless of how their compiled artifacts
    deduped.  ``drift`` is the normalized signal the online updater
    thresholds on — changed include bits relative to the reference bank's
    include count (a freshly-promoted artifact reads 0.0).
    """

    n_clauses: int
    n_clauses_changed: int
    n_bits_changed: int
    n_includes_ref: int
    n_includes_live: int

    @property
    def drift(self) -> float:
        return self.n_bits_changed / max(self.n_includes_ref, 1)

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["drift"] = self.drift
        return d


def dense_include_words(config: tm.TMConfig, ta_state) -> np.ndarray:
    """(C_raw, W) packed include words of a raw automata bank — the
    drift-tracking snapshot (no dedup, no pruning, no clustering)."""
    ta = np.asarray(ta_state)
    inc = (ta[: config.n_clauses_raw] >= 0).astype(np.uint8)
    return packetizer.pack_bits_np(inc)


def include_drift(ref_words: np.ndarray, live_words: np.ndarray) -> DriftStats:
    """Compare two dense packed include banks (same shape) bit-for-bit."""
    ref = np.asarray(ref_words, dtype=np.uint32)
    live = np.asarray(live_words, dtype=np.uint32)
    if ref.shape != live.shape:
        raise ValueError(
            f"include_drift: shape mismatch {ref.shape} vs {live.shape} — "
            "drift is only defined against the same clause bank layout")
    x = np.ascontiguousarray(ref ^ live)
    return DriftStats(
        n_clauses=int(ref.shape[0]),
        n_clauses_changed=int(x.any(axis=1).sum()) if ref.size else 0,
        n_bits_changed=int(np.unpackbits(x.view(np.uint8)).sum()),
        n_includes_ref=int(np.unpackbits(
            np.ascontiguousarray(ref).view(np.uint8)).sum()),
        n_includes_live=int(np.unpackbits(
            np.ascontiguousarray(live).view(np.uint8)).sum()),
    )


@dataclasses.dataclass
class CompiledTM:
    """Deployable inference artifact (the "bitstream" analog).

    Rows of ``include_words``/``votes`` are in :func:`cluster_order` (chain
    length, then active-word signature) so the block-sparse schedules built
    from them get chain-length-homogeneous clause blocks.  Schedules are
    memoized per ``(block_c, block_j)`` tiling — the autotuner picks the
    tiling, the artifact answers with the matching tile table.
    """

    include_words: np.ndarray   # (U, Wa) uint32 — deduped, word-compacted
    word_ids: np.ndarray        # (Wa,) int32 — active word indices into dense W
    votes: np.ndarray           # (U, n_classes) int32 — multiplicity x polarity
    n_features: int
    n_classes: int
    stats: CompileStats
    # a convolutional (ConvCoTM) artifact: its patches' geometry.  Its
    # include rows are over one patch's literals, its votes the summed
    # signed weights of each unique clause; None for a vanilla TM
    geometry: Optional[Geometry] = None
    _conv_ops: Optional[tuple] = dataclasses.field(default=None,
                                                   repr=False)
    _schedules: dict = dataclasses.field(default_factory=dict, repr=False)
    _fschedules: dict = dataclasses.field(default_factory=dict, repr=False)
    # anytime-inference metadata (kernels/anytime.py): per-tile-prefix
    # residual-swing margins, keyed like the schedule memos; quality-level
    # prefix schedules keyed (engine, schedule key, level)
    _margins: dict = dataclasses.field(default_factory=dict, repr=False)
    _fmargins: dict = dataclasses.field(default_factory=dict, repr=False)
    _prefix_schedules: dict = dataclasses.field(default_factory=dict,
                                                repr=False)
    # autotuned kernel tilings recorded against this artifact (keyed
    # "<kernel>:B<bucket>"), shipped by save() so a cold-start server loads
    # a tuned schedule instead of re-paying the sweep
    tuned: dict = dataclasses.field(default_factory=dict, repr=False)
    # candidate-independent cost-model features
    # (``kernels/cost_model.artifact_features``), shipped by save() so a
    # zoo cold-load predicts a tiling with neither timing runs nor the
    # HLO-lowering the feature extraction pays once
    features: dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def n_unique(self) -> int:
        return self.include_words.shape[0]

    def conv_operands(self):
        """The convolutional kernel's banked operands (``conv_infer.
        conv_operands``), built once per artifact."""
        from repro.kernels import conv_infer

        if self._conv_ops is None:
            g = self.geometry
            inc = packetizer.unpack_bits_np(self.include_words, g.literals)
            self._conv_ops = conv_infer.conv_operands(inc, self.votes, g)
        return self._conv_ops

    @property
    def n_words_active(self) -> int:
        return self.include_words.shape[1]

    def schedule(self, block_c: int | None = None, block_j: int | None = None):
        """Block-sparse chain schedule for this artifact at the given
        tiling (defaults from ``kernels/sparse_infer.py``), memoized."""
        from repro.kernels import sparse_infer

        key = (
            block_c or sparse_infer.DEFAULT_BLOCK_C,
            block_j or sparse_infer.DEFAULT_BLOCK_J,
        )
        if key not in self._schedules:
            self._schedules[key] = sparse_infer.build_schedule(
                self.include_words, block_c=key[0], block_j=key[1]
            )
        return self._schedules[key]

    @property
    def default_schedule(self):
        return self.schedule()

    def factorized_schedule(self, block_c: int | None = None,
                            block_j: int | None = None,
                            block_t: int | None = None,
                            term_w: int | None = None):
        """Two-level factorized (shared-term) schedule for this artifact
        at the given tiling (defaults from ``kernels/term_infer.py``;
        ``term_w=None`` auto-picks the bit-chain width), memoized."""
        from repro.kernels import term_infer

        if term_w is None:
            term_w = term_infer.pick_term_width(self.include_words)
        key = (
            block_c or term_infer.DEFAULT_BLOCK_C,
            block_j or term_infer.DEFAULT_BLOCK_J,
            block_t or term_infer.DEFAULT_BLOCK_T,
            term_w,
        )
        if key not in self._fschedules:
            self._fschedules[key] = term_infer.build_factorized_schedule(
                self.include_words, block_c=key[0], block_j=key[1],
                block_t=key[2], term_w=key[3],
            )
        return self._fschedules[key]

    @property
    def default_factorized_schedule(self):
        return self.factorized_schedule()

    def tile_margins(self, block_c: int | None = None,
                     block_j: int | None = None) -> np.ndarray:
        """(T,) residual-swing margin table for the sparse chain schedule
        at the given tiling (``kernels/anytime.py``), memoized; loaded
        artifacts ship the default-tiling table verbatim."""
        from repro.kernels import anytime, sparse_infer

        key = (
            block_c or sparse_infer.DEFAULT_BLOCK_C,
            block_j or sparse_infer.DEFAULT_BLOCK_J,
        )
        if key not in self._margins:
            self._margins[key] = anytime.sparse_tile_margins(
                self.schedule(*key), self.votes)
        return self._margins[key]

    def factorized_tile_margins(self, block_c: int | None = None,
                                block_j: int | None = None,
                                block_t: int | None = None,
                                term_w: int | None = None) -> np.ndarray:
        """(T,) residual-swing margin table for the factorized schedule at
        the given tiling, memoized."""
        from repro.kernels import anytime

        fsched = self.factorized_schedule(block_c, block_j, block_t, term_w)
        # mirror factorized_schedule's memo key exactly (term_w auto-pick)
        key = next(k for k, v in self._fschedules.items() if v is fsched)
        if key not in self._fmargins:
            self._fmargins[key] = anytime.factorized_tile_margins(
                fsched, self.votes)
        return self._fmargins[key]

    def quality_levels(self, engine: str = "sparse", **tiling) -> list:
        """Quality tiers for this artifact on the given schedule engine:
        ``[{level, n_tiles, bound, frac}, ...]`` with level 0 = exact full
        walk (bound 0) and levels 1..N progressively shorter tile prefixes
        whose error bound (``kernels/anytime.py`` semantics: the served
        class trails the true winner by at most ``bound`` votes) is the
        residual swing after the prefix."""
        from repro.kernels import anytime

        if engine == "factorized":
            fsched = self.factorized_schedule(**tiling)
            margins = self.factorized_tile_margins(**tiling)
            full, min_tiles = fsched.n_tiles, fsched.n_term_tiles + 1
        else:
            sched = self.schedule(**tiling)
            margins = self.tile_margins(**tiling)
            full, min_tiles = sched.n_tiles, 1
        levels = [dict(level=0, n_tiles=full, bound=0, frac=0.0)]
        levels.extend(anytime.quality_prefixes(
            margins, anytime.total_swing(self.votes), min_tiles=min_tiles))
        return levels

    def quality_prefix_schedule(self, level: int, engine: str = "sparse",
                                **tiling):
        """The tile-prefix schedule serving quality ``level`` (level 0
        returns the full schedule), memoized."""
        from repro.kernels import anytime

        if level <= 0:
            return (self.factorized_schedule(**tiling)
                    if engine == "factorized" else self.schedule(**tiling))
        key = (engine, tuple(sorted(tiling.items())), int(level))
        if key not in self._prefix_schedules:
            levels = self.quality_levels(engine, **tiling)
            q = levels[min(level, len(levels) - 1)]
            if engine == "factorized":
                self._prefix_schedules[key] = anytime.factorized_prefix_schedule(
                    self.factorized_schedule(**tiling), q["n_tiles"])
            else:
                self._prefix_schedules[key] = anytime.sparse_prefix_schedule(
                    self.schedule(**tiling), q["n_tiles"])
        return self._prefix_schedules[key]

    @staticmethod
    def _tuned_key(kernel: str, bucket: int, rows: int | None,
                   mode: str | None) -> str:
        key = f"{kernel}:B{int(bucket)}"
        if rows is not None:
            key += f":U{int(rows)}"      # shard-slice vs full-bank sweeps
        if mode is not None:
            key += f":{mode}"            # backend:interp|compiled
        return key

    def record_tuned(self, kernel: str, bucket: int, blocks: dict, *,
                     rows: int | None = None, mode: str | None = None) -> None:
        """Remember an autotuned tiling for this artifact (persisted by
        ``save()``): ``kernel`` is the sweep family (``sparse_infer`` /
        ``term_infer`` / ``fused_infer``), ``bucket`` the request-batch
        size the sweep ran at, ``rows`` the clause-row count the sweep
        actually saw (a mesh run tunes a per-shard SLICE — its winner must
        not answer for the full bank), and ``mode`` the backend/interpret
        tag (``kernels/autotune._mode_backend``) so a CPU-interpret tiling
        is never recalled on a compiled TPU server."""
        self.tuned[self._tuned_key(kernel, bucket, rows, mode)] = dict(blocks)

    def tuned_blocks(self, kernel: str, bucket: int, *,
                     rows: int | None = None,
                     mode: str | None = None) -> dict | None:
        """Recall a tiling recorded by :meth:`record_tuned` (or shipped
        inside a loaded artifact); None when this exact (kernel, bucket,
        rows, mode) was never tuned."""
        blocks = self.tuned.get(self._tuned_key(kernel, bucket, rows, mode))
        return dict(blocks) if blocks is not None else None

    def extract_features(self, refresh: bool = False) -> dict:
        """Candidate-independent cost-model features of this artifact
        (``kernels/cost_model.artifact_features``), memoized on the
        instance and persisted by :meth:`save`.  The HLO-derived terms
        degrade gracefully: a shape the oracle can't lower (or a backend
        without cost analysis) still yields the schedule-statistic
        features, so prediction never blocks serving."""
        if self.features and not refresh:
            return dict(self.features)
        from repro.kernels import cost_model

        try:
            feats = cost_model.artifact_features(self)
        except Exception:
            feats = cost_model.artifact_features(self, with_hlo=False)
        self.features = feats
        return dict(feats)

    def save(self, path: str) -> str:
        """Write the artifact atomically with an integrity envelope.

        The default-tiling schedules ship inside the artifact (the
        "bitstream" carries its execution schedules); other tilings are
        rebuilt on demand from the include rows.  Autotuned tilings
        recorded via record_tuned() and the cost-model feature dict ride
        in the meta JSON, so a server cold-starting from this file skips
        both the sweep and the feature extraction entirely.

        Integrity: the meta carries ``ARTIFACT_SCHEMA_VERSION`` and a
        sha256 content checksum over every array + the meta itself, and
        the file is written to a tmp path then ``os.replace``d — a SIGTERM
        mid-save can never truncate the artifact the next run will load,
        and ``load()`` rejects any byte that rotted after the replace.
        Returns the final path (``.npz`` is appended when missing, the
        same normalization ``np.savez`` applies).
        """
        if self.geometry is not None:
            # a convolutional artifact runs no schedule: its bank is all
            arrays = dict(include_words=self.include_words,
                          word_ids=self.word_ids, votes=self.votes)
            meta = dict(schema=ARTIFACT_SCHEMA_VERSION,
                        n_features=self.n_features,
                        n_classes=self.n_classes,
                        stats=self.stats.as_dict(),
                        geometry=list(self.geometry), tuned=self.tuned)
            return self._write(path, arrays, meta)
        sched = self.default_schedule
        fsched = self.default_factorized_schedule
        arrays = dict(
            include_words=self.include_words,
            word_ids=self.word_ids,
            votes=self.votes,
            sched_margin=np.asarray(self.tile_margins(), np.int64),
            fsched_margin=np.asarray(self.factorized_tile_margins(), np.int64),
            sched_chain_ids=sched.chain_ids,
            sched_tiles=np.stack([sched.tile_cb, sched.tile_jb,
                                  sched.tile_first, sched.tile_last])
            if sched.n_tiles else np.zeros((4, 0), np.int32),
            sched_counts=sched.counts,
            fsched_term_chain=fsched.term_chain,
            fsched_term_table=np.stack([
                fsched.term_word,
                fsched.term_val.astype(np.int64).astype(np.int32)])
            if fsched.n_terms else np.zeros((2, 0), np.int32),
            fsched_clause_chain=fsched.clause_chain,
            fsched_tiles=np.stack([
                fsched.tile_stage, fsched.tile_tb, fsched.tile_cb,
                fsched.tile_jb, fsched.tile_first, fsched.tile_last])
            if fsched.n_tiles else np.zeros((6, 0), np.int32),
            fsched_counts=fsched.counts,
        )
        meta = dict(
            schema=ARTIFACT_SCHEMA_VERSION,
            n_features=self.n_features,
            n_classes=self.n_classes,
            stats=self.stats.as_dict(),
            schedule=dict(block_c=sched.block_c,
                          block_j=sched.block_j,
                          n_rows=sched.n_rows,
                          n_lit_bits=sched.n_lit_bits),
            fschedule=dict(block_c=fsched.block_c,
                           block_j=fsched.block_j,
                           block_t=fsched.block_t,
                           term_w=fsched.term_w,
                           n_rows=fsched.n_rows,
                           n_terms=fsched.n_terms,
                           n_lit_bits=fsched.n_lit_bits),
            tuned=self.tuned,
            features=self.extract_features(),
        )
        return self._write(path, arrays, meta)

    @staticmethod
    def _write(path: str, arrays: dict, meta: dict) -> str:
        """Write ``arrays`` and ``meta`` under the checksum envelope, by a
        tmp file and an atomic replace; the final path."""
        meta["checksum"] = _artifact_checksum(arrays, meta)
        final = path if path.endswith(".npz") else path + ".npz"
        tmp = f"{final}.tmp-{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                np.savez_compressed(
                    f,
                    meta=np.frombuffer(json.dumps(meta).encode(), np.uint8),
                    **arrays,
                )
                f.flush()
                os.fsync(f.fileno())
            faults.raise_if("artifact.save_abort")
            os.replace(tmp, final)
        finally:
            if os.path.exists(tmp):       # failed save leaves no debris
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
        faults.corrupt_if("artifact.bitflip", final,
                          default_pos=_payload_offset(final))
        return final

    @staticmethod
    def load(path: str) -> "CompiledTM":
        """Load and VERIFY an artifact; raise :class:`ArtifactError` rather
        than ever returning one that could serve wrong predictions."""
        from repro.kernels import sparse_infer, term_infer

        try:
            z = np.load(path)
            meta = json.loads(bytes(z["meta"]).decode())
            arrays = {k: z[k] for k in z.files if k != "meta"}
        except Exception as e:
            raise ArtifactError(
                f"artifact {path} is unreadable (truncated or not a "
                f"compiled artifact): {type(e).__name__}: {e}") from e
        schema = meta.get("schema", 0)
        if schema != ARTIFACT_SCHEMA_VERSION:
            raise ArtifactError(
                f"artifact {path} has schema version {schema}; this runtime "
                f"requires {ARTIFACT_SCHEMA_VERSION} — recompile the model "
                "(compile_tm + save) instead of serving a stale artifact")
        recorded = meta.pop("checksum", None)
        recomputed = _artifact_checksum(arrays, meta)
        if recorded != recomputed:
            raise ArtifactError(
                f"artifact {path} failed its content checksum (recorded "
                f"{recorded}, recomputed {recomputed}) — the file is corrupt "
                "(bit-rot or a partial write); refusing to serve it")
        st = meta["stats"]
        stats = CompileStats(
            **{k: st[k] for k in (
                "n_clauses_dense", "n_clauses_nonempty", "n_clauses_unique",
                "n_words_dense", "n_words_active", "n_includes", "n_literals",
                "n_partial_terms_dense", "n_partial_terms_unique",
                "n_positions", "n_patch_literals", "weight_min",
                "weight_max",
            ) if k in st}
        )
        compiled = CompiledTM(
            include_words=z["include_words"],
            word_ids=z["word_ids"],
            votes=z["votes"],
            n_features=meta["n_features"],
            n_classes=meta["n_classes"],
            stats=stats,
            geometry=(Geometry(*meta["geometry"]) if "geometry" in meta
                      else None),
        )
        if "schedule" in meta:   # pre-schedule artifacts rebuild lazily
            sm = meta["schedule"]
            tiles = z["sched_tiles"]
            counts = z["sched_counts"]
            # save() ships the DEFAULT-tiling schedule; memoize it under
            # the default (requested) key — sm["block_c"] is the clipped
            # effective value, which small artifacts would never look up
            compiled._schedules[(sparse_infer.DEFAULT_BLOCK_C,
                                 sparse_infer.DEFAULT_BLOCK_J)] = (
                sparse_infer.SparseSchedule(
                    block_c=sm["block_c"], block_j=sm["block_j"],
                    n_rows=sm["n_rows"], n_lit_bits=sm["n_lit_bits"],
                    chain_ids=z["sched_chain_ids"],
                    tile_cb=tiles[0], tile_jb=tiles[1],
                    tile_first=tiles[2], tile_last=tiles[3],
                    counts=counts,
                    indptr=np.concatenate(
                        [[0], np.cumsum(counts)]).astype(np.int32),
                )
            )
            margin = np.asarray(z["sched_margin"], np.int64)
            if faults.fire_if("anytime.margin_corrupt"):
                # a producer writing wrong margins re-checksums them, so
                # the envelope passes — only validate_artifact's vote-table
                # consistency check stands between this and silently
                # skewed early-exit predictions
                margin = margin.copy()
                if margin.size:
                    margin[0] += 1
                else:
                    margin = np.array([1], np.int64)
            compiled._margins[(sparse_infer.DEFAULT_BLOCK_C,
                               sparse_infer.DEFAULT_BLOCK_J)] = margin
        if "fschedule" in meta:   # pre-factorization artifacts rebuild lazily
            fm = meta["fschedule"]
            ftiles = z["fsched_tiles"]
            fcounts = z["fsched_counts"]
            tt = z["fsched_term_table"]
            compiled._fschedules[(term_infer.DEFAULT_BLOCK_C,
                                  term_infer.DEFAULT_BLOCK_J,
                                  term_infer.DEFAULT_BLOCK_T,
                                  fm["term_w"])] = (
                term_infer.FactorizedSchedule(
                    block_c=fm["block_c"], block_j=fm["block_j"],
                    block_t=fm["block_t"], term_w=fm["term_w"],
                    n_rows=fm["n_rows"], n_terms=fm["n_terms"],
                    n_lit_bits=fm["n_lit_bits"],
                    term_word=tt[0], term_val=tt[1].astype(np.uint32),
                    term_chain=z["fsched_term_chain"],
                    clause_chain=z["fsched_clause_chain"],
                    tile_stage=ftiles[0], tile_tb=ftiles[1],
                    tile_cb=ftiles[2], tile_jb=ftiles[3],
                    tile_first=ftiles[4], tile_last=ftiles[5],
                    counts=fcounts,
                    indptr=np.concatenate(
                        [[0], np.cumsum(fcounts)]).astype(np.int32),
                )
            )
            compiled._fmargins[(term_infer.DEFAULT_BLOCK_C,
                                term_infer.DEFAULT_BLOCK_J,
                                term_infer.DEFAULT_BLOCK_T,
                                fm["term_w"])] = np.asarray(
                z["fsched_margin"], np.int64)
        compiled.tuned.update(meta.get("tuned", {}))
        compiled.features.update(meta.get("features", {}) or {})
        validate_artifact(compiled)
        return compiled


def validate_artifact(compiled: CompiledTM) -> None:
    """Structural invariant checks on an artifact and its shipped schedules.

    A second verification layer behind the checksum: the checksum catches
    bytes that changed after ``save()``, this catches an artifact that was
    *written* wrong (a buggy or adversarial producer) — out-of-range chain
    or term ids would otherwise gather-clamp into silently wrong class
    sums.  Raises :class:`ArtifactError` on the first violation.
    """

    def fail(msg: str):
        raise ArtifactError(f"artifact invariant violated: {msg}")

    inc, votes, wid = compiled.include_words, compiled.votes, compiled.word_ids
    if inc.ndim != 2:
        fail(f"include_words must be 2-D, got shape {inc.shape}")
    U, Wa = inc.shape
    if votes.shape != (U, compiled.n_classes):
        fail(f"votes shape {votes.shape} != ({U}, {compiled.n_classes})")
    if compiled.geometry is not None:
        _validate_conv(compiled, fail)
        return
    if wid.shape != (Wa,):
        fail(f"word_ids shape {wid.shape} != ({Wa},)")
    if Wa and (int(wid[0]) < 0 or (Wa > 1 and np.any(np.diff(wid) <= 0))):
        fail("word_ids must be non-negative and strictly increasing")
    n_dense = compiled.stats.n_words_dense
    if n_dense and Wa and int(wid[-1]) >= n_dense:
        fail(f"word_ids reach {int(wid[-1])} but the dense model has only "
             f"{n_dense} words — gathers would clamp")

    def check_tiles(tag, counts, indptr, n_tiles, tile_cb):
        if indptr.shape[0] != counts.shape[0] + 1 or (indptr.size and indptr[0] != 0):
            fail(f"{tag}: indptr shape/origin inconsistent with counts")
        if np.any(counts < 0) or np.any(np.diff(indptr) != counts):
            fail(f"{tag}: tile indptr is not the monotone prefix sum of counts")
        if int(counts.sum()) > n_tiles:
            fail(f"{tag}: counts claim {int(counts.sum())} tiles but the "
                 f"tile table has {n_tiles}")
        if n_tiles and (np.any(tile_cb < 0) or np.any(tile_cb >= counts.shape[0])):
            fail(f"{tag}: tile clause-block ids out of range")

    for s in compiled._schedules.values():
        if s.n_rows != U:
            fail(f"chain schedule covers {s.n_rows} rows, artifact has {U}")
        if s.n_lit_bits != 32 * Wa:
            fail(f"chain schedule n_lit_bits {s.n_lit_bits} != 32*{Wa}")
        if np.any(s.chain_ids < 0) or np.any(s.chain_ids > s.n_lit_bits):
            fail("chain ids out of range (sentinel is the maximum legal id)")
        if s.chain_ids.shape[0] > s.n_rows and not np.all(
                s.chain_ids[s.n_rows:] == s.n_lit_bits):
            fail("padded chain rows past n_rows must be all-sentinel")
        check_tiles("chain schedule", s.counts, s.indptr, s.n_tiles, s.tile_cb)

    for fs in compiled._fschedules.values():
        if fs.n_rows != U:
            fail(f"factorized schedule covers {fs.n_rows} rows, artifact has {U}")
        if fs.n_lit_bits != 32 * Wa:
            fail(f"factorized schedule n_lit_bits {fs.n_lit_bits} != 32*{Wa}")
        if np.any(fs.term_chain < 0) or np.any(fs.term_chain > fs.n_lit_bits):
            fail("term-chain literal ids out of range")
        if np.any(fs.clause_chain < 0) or np.any(fs.clause_chain > fs.n_terms):
            fail("clause-chain term ids out of range (sentinel == n_terms)")
        if fs.clause_chain.shape[0] > fs.n_rows and not np.all(
                fs.clause_chain[fs.n_rows:] == fs.n_terms):
            fail("padded clause-chain rows past n_rows must be all-sentinel")
        if fs.term_chain.shape[0] > fs.n_terms and not np.all(
                fs.term_chain[fs.n_terms:] == fs.n_lit_bits):
            fail("padded term rows past n_terms must be all-sentinel")
        if fs.term_word.shape[0] != fs.n_terms or fs.term_val.shape[0] != fs.n_terms:
            fail("term table length != n_terms")
        if fs.n_terms and (np.any(fs.term_word < 0) or np.any(fs.term_word >= Wa)):
            fail("term active-word indices out of range")
        if np.any((fs.tile_stage != 0) & (fs.tile_stage != 1)):
            fail("tile_stage entries must be 0 (term) or 1 (clause)")
        n_ctiles = int((fs.tile_stage == 1).sum())
        check_tiles("factorized schedule", fs.counts, fs.indptr, n_ctiles,
                    fs.tile_cb[fs.tile_stage == 1] if fs.n_tiles else fs.tile_cb)

    # anytime margin metadata: monotone non-increasing AND exactly the
    # residual swing the vote table implies — corrupt margins would make
    # early-exit certify too eagerly (wrong argmax) or budgeted mode
    # under-report its error bound
    def check_margins(tag, margins, sched, recompute):
        margins = np.asarray(margins)
        if margins.shape != (sched.n_tiles,):
            fail(f"{tag}: margin table shape {margins.shape} != "
                 f"({sched.n_tiles},)")
        if margins.size == 0:
            return
        if np.any(margins < 0):
            fail(f"{tag}: margin table has negative entries")
        if np.any(np.diff(margins) > 0):
            fail(f"{tag}: margin table is not monotone non-increasing")
        expect = recompute(sched, compiled.votes)
        if not np.array_equal(margins, expect):
            fail(f"{tag}: margin table is inconsistent with the vote table "
                 "(residual swing mismatch)")

    from repro.kernels import anytime

    for key, m in compiled._margins.items():
        s = compiled._schedules.get(key)
        if s is None:
            fail(f"chain margin table for unknown tiling {key}")
        check_margins("chain margins", m, s, anytime.sparse_tile_margins)
    for key, m in compiled._fmargins.items():
        fs = compiled._fschedules.get(key)
        if fs is None:
            fail(f"factorized margin table for unknown tiling {key}")
        check_margins("factorized margins", m, fs,
                      anytime.factorized_tile_margins)


def _validate_conv(compiled: CompiledTM, fail) -> None:
    """Invariants of a convolutional artifact: a geometry that makes
    patches, include rows exactly one patch's literals wide with no bit
    past them, every word kept, weights within the kernel's exact fold."""
    from repro.kernels.fused_infer import VOTE_BOUND

    g = compiled.geometry
    if any(int(v) <= 0 for v in g):
        fail(f"geometry {g} is not three positive sizes (H, W, window)")
    if g.win > min(g.H, g.W):
        fail(f"window {g.win} exceeds the image {g.H}x{g.W}")
    if compiled.n_features != g.H * g.W:
        fail(f"n_features {compiled.n_features} != {g.H}x{g.W} pixels")
    inc, wid = compiled.include_words, compiled.word_ids
    Wl = packetizer.n_words(g.literals)
    if inc.shape[1] != Wl:
        fail(f"include rows have {inc.shape[1]} words; {g.literals} patch "
             f"literals take {Wl}")
    if not np.array_equal(wid, np.arange(Wl)):
        fail("a convolutional artifact keeps every include word")
    tail = g.literals - 32 * (Wl - 1)
    if tail < 32 and inc.size and np.any(inc[:, -1] >> np.uint32(tail)):
        fail("include bits past the last patch literal")
    if compiled.votes.size and int(np.abs(compiled.votes).max()) >= VOTE_BOUND:
        fail(f"|weights| reach {int(np.abs(compiled.votes).max())}, the "
             f"kernel's fold is exact below {VOTE_BOUND}")
    st = compiled.stats
    if (st.n_positions, st.n_patch_literals) != (g.positions, g.literals):
        fail(f"stats count {st.n_positions} positions x "
             f"{st.n_patch_literals} literals; the geometry gives "
             f"{g.positions} x {g.literals}")


def _compile_conv(config: tm.ConvTMConfig, ta_state, weights, *,
                  dedup: bool) -> CompiledTM:
    """Empty clauses dropped, identical include rows merged (their weights
    summed); every include word kept (the kernel reads a whole patch)."""
    g = config.geometry
    ta, w = np.asarray(ta_state), np.asarray(weights)
    C, K = config.n_clauses, config.n_classes
    if ta.shape != (C, g.literals) or w.shape != (C, K):
        raise ValueError(
            f"a {C}-clause ConvCoTM over {g.literals} patch literals and "
            f"{K} classes needs automata ({C}, {g.literals}) and weights "
            f"({C}, {K}); got {ta.shape} and {w.shape}")
    inc = (ta >= 0).astype(np.uint8)
    nonempty = inc.any(axis=1)
    Wl = packetizer.n_words(g.literals)
    words = (packetizer.pack_bits_np(inc[nonempty]) if nonempty.any()
             else np.zeros((0, Wl), np.uint32))
    if dedup and words.shape[0]:
        uniq, inv = np.unique(words, axis=0, return_inverse=True)
        inv = inv.reshape(-1)
    else:
        uniq, inv = words, np.arange(words.shape[0])
    U = uniq.shape[0]
    votes = np.zeros((max(U, 1), K), np.int32)
    np.add.at(votes, inv, w[nonempty].astype(np.int32))
    if U == 0:
        uniq = np.zeros((1, Wl), np.uint32)   # degenerate all-empty model
    stats = CompileStats(
        n_clauses_dense=C,
        n_clauses_nonempty=int(nonempty.sum()),
        n_clauses_unique=int(uniq.shape[0]),
        n_words_dense=Wl,
        n_words_active=Wl,
        n_includes=int(inc.sum()),
        n_literals=g.literals,
        n_positions=g.positions,
        n_patch_literals=g.literals,
        weight_min=int(votes.min()),
        weight_max=int(votes.max()),
    )
    return CompiledTM(
        include_words=uniq.astype(np.uint32),
        word_ids=np.arange(Wl, dtype=np.int32),
        votes=votes,
        n_features=config.n_features,
        n_classes=K,
        stats=stats,
        geometry=g,
    )


def compile_tm(
    config,
    ta_state,
    *,
    weights=None,
    dedup: bool = True,
    prune_words: bool = True,
    cluster: bool = True,
) -> CompiledTM:
    """Compile a trained automata bank into a :class:`CompiledTM`.

    A :class:`tm.ConvTMConfig` compiles a convolutional artifact from its
    ``(C, Lp)`` automata over one patch's literals and its ``(C, K)``
    signed ``weights``; the word and row options below are for a vanilla
    :class:`tm.TMConfig`.

    ``cluster`` reorders the surviving unique clauses by (chain length,
    active-word signature) — the row order the block-sparse schedule wants;
    votes move with their rows, so class sums are invariant.
    ``dedup=False, prune_words=False, cluster=False`` is the
    DON'T-TOUCH-pragma analog used by benchmarks/logic_sharing.py to
    measure the savings (paper Fig. 8).
    """
    if isinstance(config, tm.ConvTMConfig):
        if weights is None:
            raise TypeError("compile_tm: a ConvCoTM needs its weights=")
        return _compile_conv(config, ta_state, weights, dedup=dedup)
    ta = np.asarray(ta_state)
    C_raw = config.n_clauses_raw
    inc = (ta[:C_raw] >= 0).astype(np.uint8)               # (C, L)
    pol = np.where(np.arange(C_raw) % 2 == 0, 1, -1).astype(np.int32)
    cls = np.arange(C_raw) // config.clauses_per_class

    nonempty = inc.any(axis=1)
    inc_ne = inc[nonempty]
    pol_ne = pol[nonempty]
    cls_ne = cls[nonempty]
    n_nonempty = int(inc_ne.shape[0])

    words_dense = packetizer.pack_bits_np(inc_ne) if n_nonempty else np.zeros(
        (0, packetizer.n_words(config.n_literals)), np.uint32
    )
    W = packetizer.n_words(config.n_literals)

    if dedup and n_nonempty:
        uniq, inv = np.unique(words_dense, axis=0, return_inverse=True)
    else:
        uniq, inv = words_dense, np.arange(n_nonempty)
    U = uniq.shape[0]

    votes = np.zeros((max(U, 1), config.n_classes), np.int32)
    if n_nonempty:
        np.add.at(votes, (inv, cls_ne), pol_ne)
    if U == 0:
        uniq = np.zeros((1, W), np.uint32)  # degenerate all-empty model
        U = 1

    if prune_words:
        active = uniq.any(axis=0)
        if not active.any():
            active[:1] = True
        word_ids = np.nonzero(active)[0].astype(np.int32)
    else:
        word_ids = np.arange(uniq.shape[1], dtype=np.int32)
    uniq = uniq[:, word_ids]

    votes = votes[:U]
    if cluster and U > 1:
        from repro.kernels import anytime, sparse_infer

        # vote-mass bands (|polarity x multiplicity| descending) so the
        # anytime margin decays steeply, density-clustered within bands so
        # tile counts stay near the pure-clustered layout
        order = anytime.margin_order(uniq, votes,
                                     cluster_fn=sparse_infer.cluster_order)
        uniq = uniq[order]
        votes = votes[order]

    # partial-clause sharing opportunity: unique nonzero include words per
    # word column (zero words are free — they never gate anything)
    nonzero_terms = int((uniq != 0).sum())
    unique_terms = sum(
        len(np.unique(col[col != 0])) for col in uniq.T
    )
    stats = CompileStats(
        n_clauses_dense=C_raw,
        n_clauses_nonempty=n_nonempty,
        n_clauses_unique=int(U),
        n_words_dense=int(W),
        n_words_active=int(word_ids.shape[0]),
        n_includes=int(inc.sum()),
        n_literals=config.n_literals,
        n_partial_terms_dense=nonzero_terms,
        n_partial_terms_unique=int(unique_terms),
    )
    return CompiledTM(
        include_words=uniq.astype(np.uint32),
        word_ids=word_ids,
        votes=votes,
        n_features=config.n_features,
        n_classes=config.n_classes,
        stats=stats,
    )


def incremental_recompile(
    config: tm.TMConfig,
    ta_state,
    prev: CompiledTM,
    *,
    dedup: bool = True,
    prune_words: bool = True,
    cluster: bool = True,
) -> tuple[CompiledTM, dict]:
    """Recompile a drifted bank, reusing ``prev``'s schedule work where the
    layout survived.

    The host compile pipeline itself (:func:`compile_tm`) is cheap numpy;
    the expensive artifact state is the chain SCHEDULE (a per-clause python
    compaction loop) and the autotuned tilings.  When the new artifact
    lands on the same word layout and row count as ``prev`` — the common
    case for small online drift — the default-tiling chain schedule is
    rebuilt incrementally (``sparse_infer.build_schedule_incremental``:
    only clauses whose include rows moved are re-compacted) and ``prev``'s
    tuned tilings carry over.  Any layout change falls back to the full
    lazy rebuild.

    Returns ``(compiled, info)``; ``info["mode"]`` is ``"incremental"`` or
    ``"full"``, with ``rows_reused``/``tiles_reused`` counters in the
    incremental case.  Either way the result is bit-identical to a
    from-scratch ``compile_tm`` (the incremental schedule is exact, and
    the factorized schedule stays lazy).
    """
    from repro.kernels import sparse_infer

    new = compile_tm(config, ta_state, dedup=dedup,
                     prune_words=prune_words, cluster=cluster)
    info: dict = dict(mode="full", rows_reused=0, tiles_reused=0)
    key = (sparse_infer.DEFAULT_BLOCK_C, sparse_infer.DEFAULT_BLOCK_J)
    prev_sched = prev._schedules.get(key)
    if (prev_sched is not None
            and new.include_words.shape == prev.include_words.shape
            and np.array_equal(new.word_ids, prev.word_ids)):
        sched, re_info = sparse_infer.build_schedule_incremental(
            new.include_words, prev_sched, prev.include_words,
            block_c=key[0], block_j=key[1])
        new._schedules[key] = sched
        info = dict(mode="incremental", **re_info)
        # same shape family: prev's swept/predicted tilings remain valid
        # keys (kernel:bucket[:rows][:mode]) for the successor artifact
        new.tuned.update({k: dict(v) for k, v in prev.tuned.items()})
    return new, info


# the tiling keys each engine takes; the oracle has no tiling and takes
# them all, so a call tuned for a kernel still runs on the last rung
_ENGINE_BLOCKS = {
    "factorized": {"block_c", "block_j", "block_s", "block_t", "term_w"},
    "sparse": {"block_c", "block_j", "block_s"},
    "dense": {"block_b", "block_c", "block_w"},
    "conv": {"block_b"},
}
_ALL_BLOCKS = set().union(*_ENGINE_BLOCKS.values())


def run_compiled(
    compiled: CompiledTM,
    x_packed: jnp.ndarray,
    *,
    engine: str = "auto",
    interpret: bool | None = None,
    quality: int = 0,
    early_exit: bool = False,
    **blocks,
) -> jnp.ndarray:
    """Inference with the compiled artifact: (B, W_dense) packed literals ->
    (B, n_classes) int32 class sums.

    ``engine`` is one of ``ops.ENGINE_NAMES``: ``"auto"`` (default) is
    ``ops.engine_levels(compiled)[0]``, the head of the ladder the
    artifact is served on; ``"factorized"`` the two-level shared-term
    schedule kernel (``kernels/term_infer.py``), ``"sparse"`` the flat
    block-sparse chain kernel (``kernels/sparse_infer.py``), ``"dense"``
    the fused dense kernel (``kernels/fused_infer.py``), ``"conv"`` the
    convolutional kernel (a convolutional artifact's one kernel), and
    ``"oracle"`` the pure-jnp XLA reference.  All engines are
    bit-identical.  ``interpret=None`` compiles the kernels on a TPU and
    interprets them elsewhere.  Empty-clause masking is unnecessary here
    — compilation already dropped empty clauses (the degenerate all-empty
    artifact keeps one all-zero clause whose votes are zero).

    Tiling comes from ``blocks``: ``block_c``/``block_j`` (chain tiling,
    memoized on the artifact) and ``block_s`` (sample slab) for the
    schedule kernels, plus ``block_t``/``term_w`` (term-table tiling) for
    the factorized one; ``block_b``/``block_c``/``block_w`` for the dense
    kernel; ``block_b`` for the conv kernel.  A key the engine does not
    take raises rather than being dropped; the oracle has no tiling and
    ignores them.

    Anytime inference (``kernels/anytime.py``): ``quality > 0`` serves a
    budgeted tile prefix (error bounded by the artifact's margin
    metadata — ``compiled.quality_levels()``), ``early_exit=True`` runs
    the exact early-exit kernel mode (argmax-identical to the full walk).
    Both apply only on the schedule-kernel paths; the dense and oracle
    engines always serve exact sums (a stronger answer than requested, so
    ladder degradation stays safe).
    """
    from repro.kernels import ops

    if engine not in ops.ENGINE_NAMES:
        raise ValueError(f"unknown engine {engine!r}; one of "
                         f"{ops.ENGINE_NAMES}")
    unknown = blocks.keys() - _ALL_BLOCKS
    if unknown:
        # a typo like block_ww=8 would otherwise serve at default tilings
        # while the caller believes their tuning applied
        raise TypeError(f"run_compiled: unknown block kwargs {sorted(unknown)}; "
                        f"expected a subset of {sorted(_ALL_BLOCKS)}")
    if engine == "auto":
        engine = ops.engine_levels(compiled)[0]
    conv = compiled.geometry is not None
    if conv and engine not in ("conv", "oracle"):
        raise TypeError(
            f"run_compiled: engine {engine!r} runs vanilla TM artifacts; "
            "a convolutional one runs on 'conv' or 'oracle'")
    if not conv and engine == "conv":
        raise TypeError("run_compiled: engine 'conv' runs convolutional "
                        "artifacts; this one is a vanilla TM")
    takes = _ALL_BLOCKS if engine == "oracle" else _ENGINE_BLOCKS[engine]
    extra = blocks.keys() - takes
    if extra:
        raise TypeError(f"run_compiled: engine {engine!r} takes no tiling "
                        f"keys {sorted(extra)}; it takes {sorted(takes)}")
    uk = engine != "oracle"
    _, it = ops.kernel_dispatch(None, interpret)
    if conv:
        return ops.conv_tm_forward_packed(
            x_packed, compiled.include_words, compiled.votes,
            geom=compiled.geometry,
            operands=compiled.conv_operands() if uk else None,
            use_kernel=uk, interpret=it, **blocks)

    xw = x_packed[:, jnp.asarray(compiled.word_ids)]        # dead-word elim
    votes = jnp.asarray(compiled.votes)
    if engine == "factorized":
        ftiling = dict(block_c=blocks.get("block_c"),
                       block_j=blocks.get("block_j"),
                       block_t=blocks.get("block_t"),
                       term_w=blocks.get("term_w"))
        if quality > 0:
            fsched = compiled.quality_prefix_schedule(
                quality, "factorized", **ftiling)
        else:
            fsched = compiled.factorized_schedule(**ftiling)
        margin = None
        if early_exit and quality <= 0 and fsched.n_tiles:
            margin = jnp.asarray(
                compiled.factorized_tile_margins(**ftiling), jnp.int32)
        return ops.tm_forward_factorized(
            xw, compiled.include_words, votes, fsched,
            use_kernel=True, interpret=it,
            block_s=blocks.get("block_s"), tile_margin=margin,
        )
    if engine == "sparse":
        stiling = dict(block_c=blocks.get("block_c"),
                       block_j=blocks.get("block_j"))
        if quality > 0:
            sched = compiled.quality_prefix_schedule(
                quality, "sparse", **stiling)
        else:
            sched = compiled.schedule(**stiling)
        margin = None
        if early_exit and quality <= 0 and sched.n_tiles:
            margin = jnp.asarray(compiled.tile_margins(**stiling), jnp.int32)
        return ops.tm_forward_schedule(
            xw, compiled.include_words, votes, sched,
            use_kernel=True, interpret=it,
            block_s=blocks.get("block_s"), tile_margin=margin,
        )
    return ops.tm_forward_packed(
        xw, jnp.asarray(compiled.include_words), votes, None,
        use_kernel=uk, interpret=it, **blocks,
    )


def predict_compiled(compiled: CompiledTM, x: jnp.ndarray, **kw) -> jnp.ndarray:
    """(B, F) raw boolean features (a convolutional artifact: the images'
    pixels, row-major) -> predicted class ids."""
    xp = (packetizer.pack_bits(x) if compiled.geometry is not None
          else packetizer.pack_literals(x))
    return jnp.argmax(run_compiled(compiled, xp, **kw), axis=-1)
