"""Join operators: build side + lookup probe.

Reference parity: ``HashBuilderOperator`` (PagesIndex ->
``PartitionedLookupSourceFactory`` future) and ``LookupJoinOperator``
(compiled JoinProbe), plus ``SetBuilderOperator``/``HashSemiJoinOperator``
for IN/EXISTS [SURVEY §2.1, §3.4; reference tree unavailable, paths
reconstructed].

TPU-first: the LookupSource is a *sorted key array* + row-index
permutation (``ops.join.build_lookup``); probing is vectorized binary
search. The build result is passed to the probe step as traced
arguments, so one compiled probe program serves every probe batch.

Join types: inner / left (probe-outer) / semi / anti. Unique-build-key
joins (FK->PK — most TPC-H joins) keep probe-batch alignment (no
expansion); duplicate-key joins expand through a static output
capacity with overflow detection.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import Batch, Column
from presto_tpu.exec.operators import (
    CapacityOverflow,
    CollectingOperator,
    Operator,
    held_concat,
)
from presto_tpu.expr import Expr, InputRef, evaluate, param_scope
from presto_tpu.runtime.trace import span as trace_span
from presto_tpu.runtime.trace import sync as trace_sync
from presto_tpu.ops.groupby import gather_padded
from presto_tpu.ops.join import (
    BuildSide,
    DenseSide,
    UniqueProbe,
    build_dense,
    build_lookup,
    probe_exists,
    probe_exists_dense,
    probe_expand,
    probe_unique,
    probe_unique_dense,
    sorted_positions,
)
from presto_tpu.ops.hashing import bloom_build
from presto_tpu.ops.pallas_mode import count_program
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.spi import batch_capacity

import numpy as _np

_I64_SENTINEL = _np.int64(_np.iinfo(_np.int64).max)

#: candidate window scanned per probe row on hash-key (verify) unique
#: probes: covers collision runs of up to this many equal hashed keys
VERIFY_CANDIDATES = 4


def _pad_sp(d):
    """PAD SPACE normalization for BYTES equality in verify compares
    (mirrors expr._pad_space: zero padding compares as spaces, so a
    space-padded computed string matches zero-padded storage)."""
    if d.ndim > 1:
        return jnp.where(d == 0, jnp.uint8(32), d)
    return d


def long_dup_runs_flag(sorted_keys):
    """Traced bool: some non-sentinel key run exceeds VERIFY_CANDIDATES.

    The single definition both refusal sites use (operator build and
    the distributed repartition step) — the verified probe's candidate
    window and this detector must stay in lockstep."""
    sk = sorted_keys
    K = VERIFY_CANDIDATES
    return jnp.any((sk[K:] == sk[:-K]) & (sk[K:] != _I64_SENTINEL))


def verify_mask(verify, probe_batch: Batch, payload: Batch,
                build_row, probe_row=None, init=None):
    """AND together the by-value equality checks for hash-key verify
    pairs — the one implementation of the PAD-SPACE-normalized compare
    (probe value vs build payload value gathered through ``build_row``;
    with ``probe_row`` the probe side is gathered too, using asymmetric
    0/1 fills so out-of-range sentinel rows can never compare equal)."""
    mask = init
    for pe, be in verify:
        pv = evaluate(pe, probe_batch)
        bv = evaluate(be, payload)
        pd_ = _pad_sp(pv.data)
        if probe_row is not None:
            pd_ = gather_rows(pd_, probe_row, 0)
            bd = gather_rows(_pad_sp(bv.data), build_row, 1)
        else:
            bd = gather_rows(_pad_sp(bv.data), build_row, 1)
        eq = pd_ == bd
        if eq.ndim > 1:
            eq = eq.all(axis=1)
        mask = eq if mask is None else (mask & eq)
    return mask


def gather_rows(data, idx, fill):
    """gather_padded for 1-D or 2-D (BYTES) column data."""
    cap = data.shape[0]
    safe = jnp.minimum(idx, cap - 1)
    picked = data[safe]
    cond = idx < cap
    if picked.ndim > 1:
        cond = cond[:, None]
    return jnp.where(cond, picked, fill)


class JoinBuildOperator(CollectingOperator):
    """Collects the build side; ``finish()`` publishes the lookup
    source (sorted keys + payload batch). The downstream probe operator
    holds a reference — the LookupSourceFactory seam."""

    def __init__(
        self,
        key: Expr,
        capacity: int | None = None,
        dense_domain: tuple[int, int] | None = None,
        key_max: int | None = None,
        filter_bits: int = 0,
        params: Sequence = (),
    ):
        """``dense_domain``: optional (key_min, domain) from planner
        stats — builds a dense direct-address table alongside the sorted
        keys so unique/semi probes become a single gather (no probe
        sort). Stats are advisory: a key outside the domain at runtime
        just discards the dense side and keeps the sorted fallback.

        ``key_max``: stats upper bound on a NON-NEGATIVE key — when
        key_bits + capacity_bits <= 62, build rows sort as one packed
        (key << bits | row) int64 and the sorted unique probe needs ONE
        gather per row instead of two. Advisory like dense_domain: a
        violating key trips ``sentinel_hit`` and the query refuses
        loudly rather than mispacking.

        ``filter_bits``: when > 0, the build additionally derives the
        sideways-information-passing products — build-key min/max plus
        a two-hash Bloom bitmask of this many bits — published as
        ``filter_minmax``/``filter_bloom`` for probe-side scan
        pushdown."""
        super().__init__()
        self.key = key
        #: literal-slot values of the owning query (traced step arg)
        self._params = tuple(params)
        self.capacity = capacity
        self.dense_domain = dense_domain
        self.key_max = key_max
        self.filter_bits = filter_bits
        self.pack_bits: int | None = None
        self.build_side: BuildSide | None = None
        self.dense_side: DenseSide | None = None
        #: (min, max) 0-d device scalars over live build keys, and the
        #: Bloom words array — the runtime-join-filter products (set
        #: when filter_bits > 0 and the build is non-empty)
        self.filter_minmax = None
        self.filter_bloom = None
        self.payload: Batch | None = None
        #: True when some sorted-key run exceeds VERIFY_CANDIDATES —
        #: hash-key verified probes must refuse (see finish())
        self.long_dup_runs: bool = False

    def finish(self) -> list[Batch]:
        if not self.batches:
            # empty build needs planner-synthesized payload schema
            raise RuntimeError("empty build side not yet supported")
        batch = held_concat(self.batches)
        cap = self.capacity or batch_capacity(batch.capacity, minimum=16)
        dd = self.dense_domain

        if self.key_max is not None and self.key_max >= 0:
            pb = int(batch.capacity).bit_length()
            if int(self.key_max).bit_length() + pb <= 62:
                self.pack_bits = pb

        from presto_tpu.cache.exec_cache import EXEC_CACHE, trace_probe

        key_expr, pack_bits = self.key, self.pack_bits
        fbits = self.filter_bits

        def make_build():
            @jax.jit
            def join_build_step(b: Batch, params=()):
                trace_probe()
                with param_scope(params):
                    return body(b)

            def body(b: Batch):
                v = evaluate(key_expr, b)
                live = b.live & v.valid
                side = build_lookup(v.data, live, cap, pack_bits=pack_bits)
                dense = build_dense(v.data, live, dd[0], dd[1]) if dd else None
                filt = None
                if fbits:
                    k64 = v.data.astype(jnp.int64)
                    fmn = jnp.min(jnp.where(live, k64, _I64_SENTINEL))
                    fmx = jnp.max(jnp.where(live, k64, -_I64_SENTINEL - 1))
                    filt = (fmn, fmx, bloom_build(v.data, live, fbits))
                # key-run length > VERIFY_CANDIDATES detector: hash-key
                # probes scan a fixed candidate window per probe row, so a
                # longer collision run (>= 5 distinct strings sharing one
                # 63-bit hash — astronomically unlikely) must be refused,
                # not silently mis-probed
                return (side, dense, long_dup_runs_flag(side.sorted_keys),
                        filt)

            return join_build_step

        # shared across queries: the closure bakes in only (key expr,
        # capacity, dense domain, pack bits, filter bits)
        # — all in the content key
        build = EXEC_CACHE.get_or_build(
            EXEC_CACHE.key_of("join_build", key_expr, cap, dd, pack_bits,
                              fbits),
            make_build,
        )
        # the step reads the key's columns alone, so one program serves
        # every payload a build of this key and capacity carries
        from presto_tpu.plan.prune import expr_refs

        refs: set = set()
        expr_refs(key_expr, refs)
        keyed = batch.select([n for n in batch.names if n in refs])
        with trace_span("step:join_build", "step", {"capacity": cap}):
            side, dense, long_runs, filt = build(keyed, self._params)
        # the build's flags are the first host reads after its dispatch:
        # the host waits here for the whole build (sort included)
        with trace_sync("join_build"):
            overflow = bool(side.overflow)
            sentinel_hit = bool(side.sentinel_hit)
            long_dup_runs = bool(long_runs)
            dense_ok = dense is not None and not bool(dense.overflow)
        if filt is not None:
            self.filter_minmax = (filt[0], filt[1])
            self.filter_bloom = filt[2]
        if overflow:
            raise CapacityOverflow("JoinBuild", cap, int(side.n_rows))
        if sentinel_hit:
            if self.pack_bits is not None:
                raise NotImplementedError(
                    "a join build key violated its advisory stats bound "
                    f"(key_max={self.key_max}, pack_bits={self.pack_bits}: "
                    f"packable range is [0, 2^{62 - self.pack_bits})) — "
                    "stale or wrong connector stats")
            raise NotImplementedError(
                "a join build key equals the reserved int64 sentinel "
                f"({np.iinfo(np.int64).max}); such keys are "
                "indistinguishable from dead slots and would silently "
                "lose their matches"
            )
        self.build_side = side
        self.long_dup_runs = long_dup_runs
        # dictionary provenance for the probe-side runtime guard:
        # dictionary codes are only comparable within ONE dictionary
        self.key_dict = (
            batch[self.key.name].dictionary
            if isinstance(self.key, InputRef) and self.key.name in batch
            else None
        )
        if dense_ok:
            self.dense_side = dense
        self.payload = batch
        return []


@dataclass(frozen=True)
class BuildOutput:
    """One build-side payload column to emit: (source col, output name)."""

    source: str
    name: str


class LookupJoinOperator(Operator):
    """Probe operator. join_type: inner | left | semi | anti.

    - unique=True: FK->PK fast path, probe-aligned output (no
      expansion); duplicates on the build side would silently drop
      matches, so the planner must only set it when build keys are
      unique (PK side).
    - unique=False: expansion join with static ``out_capacity``.
    """

    def __init__(
        self,
        build: JoinBuildOperator,
        probe_key: Expr,
        build_outputs: Sequence[BuildOutput] = (),
        join_type: str = "inner",
        unique: bool = True,
        out_capacity: int | None = None,
        verify: Sequence[tuple[Expr, Expr]] = (),
        params: Sequence = (),
    ):
        """``verify``: (probe_expr, build_expr) pairs re-checked on the
        original values after a hash-key probe — wide string keys probe
        on a 63-bit hash (expr ``bytes_hash``), so candidate matches
        must be confirmed by comparing the actual bytes (the module
        docstring's collision-verification contract). Unique probes
        only."""
        self.build = build
        self.probe_key = probe_key
        self._params = tuple(params)
        self.build_outputs = list(build_outputs)
        self.join_type = join_type
        self.unique = unique
        self.out_capacity = out_capacity
        self.verify = list(verify)
        self._step = None
        self._full_step = None
        self._strategy = None

    def _record_strategy(self, name: str):
        """Count the chosen probe strategy ONCE per operator (the
        ``join.strategy.*`` observability counters)."""
        if self._strategy is None:
            self._strategy = name
            REGISTRY.counter(f"join.strategy.{name}").add()
            count_program("join", False)

    def _make_unique_probe(self, use_dense: bool):
        """Probe-aligned unique lookup closure: (build_row, matched).

        Closes over LOCALS only (key expr, verify pairs, pack bits) so
        the steps embedding it can be shared across queries through the
        executable cache without pinning this operator.

        Without verify pairs this is the plain 1-candidate probe. With
        verify pairs (hash keys) it is the collision-run scanning
        ``verified_unique_probe`` below."""
        key = self.probe_key
        verify = tuple(self.verify)
        pack_bits = self.build.pack_bits
        if verify:
            assert not use_dense, "dense sides never carry hash verify keys"

            def probe(side, payload: Batch, batch: Batch):
                return verified_unique_probe(side, key, verify, payload,
                                             batch)

            return probe

        def probe(side, payload: Batch, batch: Batch):
            v = evaluate(key, batch)
            if use_dense:
                return probe_unique_dense(side, v.data, batch.live & v.valid)
            return probe_unique(side, v.data, batch.live & v.valid,
                                pack_bits=pack_bits)

        return probe

    def _ensure_step(self):
        from presto_tpu.cache.exec_cache import EXEC_CACHE, trace_probe

        if self._step is not None:
            return
        jt, unique = self.join_type, self.unique
        outs = tuple(self.build_outputs)
        key = self.probe_key
        verify = tuple(self.verify)
        # the dense direct-address probe (one gather, no probe sort)
        # applies whenever the build published a dense side; trace-time
        # choice, so each compiled step contains exactly one kernel —
        # and use_dense/pack_bits are part of the cache key, so a
        # shared step always embeds the right kernel
        use_dense = self.build.dense_side is not None
        pack_bits = self.build.pack_bits

        if jt in ("semi", "anti"):
            assert not verify, (
                "hash-key verification requires unique probes; the "
                "planner must not route wide-key semi joins here"
            )

            def make_semi():
                def step(side, payload: Batch, batch: Batch, params=()) -> Batch:
                    trace_probe()
                    with param_scope(params):
                        v = evaluate(key, batch)
                        probe = (probe_exists_dense if use_dense
                                 else probe_exists)
                        exists = probe(side, v.data, batch.live & v.valid)
                        keep = (exists if jt == "semi"
                                else batch.live & ~exists)
                        return batch.with_live(batch.live & keep)

                step.__name__ = f"probe_{jt}_step"
                return jax.jit(step)

            self._step = EXEC_CACHE.get_or_build(
                EXEC_CACHE.key_of("lookup_semi", key, jt, use_dense),
                make_semi,
            )
            return

        if unique:
            if verify and self.build.long_dup_runs:
                raise NotImplementedError(
                    "hash-key collision run exceeds the verified probe's "
                    f"candidate window ({VERIFY_CANDIDATES})"
                )
            unique_probe = self._make_unique_probe(use_dense)

            def make_unique():
                def step(side, payload: Batch, batch: Batch, params=()) -> Batch:
                    trace_probe()
                    with param_scope(params):
                        res = unique_probe(side, payload, batch)
                        matched = res.matched
                        cols = dict(batch.columns)
                        for bo in outs:
                            src = payload[bo.source]
                            data = gather_rows(src.data, res.build_row, 0)
                            valid = gather_padded(src.valid, res.build_row,
                                                  False)
                            cols[bo.name] = Column(data, valid & matched,
                                                   src.dtype, src.dictionary)
                        live = (batch.live & matched if jt == "inner"
                                else batch.live)
                        return Batch(cols, live)

                step.__name__ = f"probe_{jt}_step"
                return jax.jit(step)

            self._step = EXEC_CACHE.get_or_build(
                EXEC_CACHE.key_of("lookup_unique", key, outs, jt, verify,
                                  use_dense, pack_bits),
                make_unique,
            )
            return

        out_cap = self.out_capacity
        assert out_cap is not None, "expansion join requires out_capacity"
        # verification on an expansion join is exact for INNER only: a
        # collision adds a spurious pair that the equality check drops;
        # under LEFT semantics an all-collision probe row would need to
        # become a null-extended row instead (not implemented)
        assert not (verify and jt != "inner"), (
            "hash-key verification on expansion joins is inner-only"
        )
        left = jt == "left"

        def make_expand():
            def step(side: BuildSide, payload: Batch, batch: Batch,
                     params=()):
                trace_probe()
                with param_scope(params):
                    return body(side, payload, batch)

            def body(side: BuildSide, payload: Batch, batch: Batch):
                v = evaluate(key, batch)
                res = probe_expand(side, v.data, batch.live & v.valid, out_cap,
                                   left=left, emit_live=batch.live)
                live = verify_mask(verify, batch, payload, res.build_row,
                                   probe_row=res.probe_row, init=res.live)
                cols = {}
                for name in batch.names:
                    src = batch[name]
                    cols[name] = Column(
                        gather_rows(src.data, res.probe_row, 0),
                        gather_padded(src.valid, res.probe_row, False),
                        src.dtype,
                        src.dictionary,
                    )
                for bo in outs:
                    src = payload[bo.source]
                    cols[bo.name] = Column(
                        gather_rows(src.data, res.build_row, 0),
                        gather_padded(src.valid, res.build_row, False),
                        src.dtype,
                        src.dictionary,
                    )
                return Batch(cols, live), res.overflow

            step.__name__ = f"probe_{jt}_step"
            return jax.jit(step)

        self._step = EXEC_CACHE.get_or_build(
            EXEC_CACHE.key_of("lookup_expand", key, outs, jt, verify,
                              out_cap, left),
            make_expand,
        )

    def _check_probe_dict(self, batch: Batch):
        """Runtime backstop for dictionary-encoded keys the planner
        could not trace to a source dictionary: joining code spaces of
        two DIFFERENT dictionaries would be silently wrong, so refuse."""
        k = self.probe_key
        if not (isinstance(k, InputRef) and k.name in batch):
            return
        pdict = batch[k.name].dictionary
        bdict = getattr(self.build, "key_dict", None)
        if pdict is not None and bdict is not None and pdict is not bdict:
            raise NotImplementedError(
                "join keys are encoded against different dictionaries "
                "and their provenance was not visible to the planner; "
                "codes are not comparable across dictionaries"
            )

    def process(self, batch: Batch) -> list[Batch]:
        assert self.build.build_side is not None, "build side not finished"
        self._check_probe_dict(batch)
        self._ensure_step()
        if self.unique or self.join_type in ("semi", "anti"):
            side = (
                self.build.dense_side
                if self.build.dense_side is not None
                else self.build.build_side
            )
            self._record_strategy(
                "dense" if self.build.dense_side is not None else "unique")
            # what the probe pays for, live or not (a static shape)
            REGISTRY.counter("exec.probe.slots").add(batch.capacity)
            with trace_span(f"step:probe_{self.join_type}", "step"):
                return [self._step(side, self.build.payload, batch,
                                   self._params)]
        self._record_strategy("expand")
        with trace_span(f"step:probe_{self.join_type}", "step"):
            out, overflow = self._step(self.build.build_side,
                                       self.build.payload, batch,
                                       self._params)
        with trace_sync("probe_overflow"):
            overflow = bool(overflow)
        if overflow:
            raise CapacityOverflow("LookupJoin", self.out_capacity)
        return [out]

    # ---- FULL OUTER probe pass -------------------------------------------
    # join_type "full" probes with LEFT semantics while accumulating a
    # matched-flags array over the build payload; after the probe stream
    # is exhausted, ``full_tail`` emits the never-matched build rows with
    # NULL probe columns (the reference's unmatched-build emission half
    # of a full outer LookupJoin [SURVEY §2.1 operator row]). Flags are
    # caller-owned so replayable streams restart them per replay and the
    # expansion path's capacity retries can discard a failed attempt's
    # partial update (the scatter is idempotent).

    def _ensure_full_step(self):
        from presto_tpu.cache.exec_cache import EXEC_CACHE, trace_probe

        if self._full_step is not None:
            return
        outs = tuple(self.build_outputs)
        key = self.probe_key
        verify = tuple(self.verify)
        use_dense = self.build.dense_side is not None
        pack_bits = self.build.pack_bits

        if self.unique:
            if verify and self.build.long_dup_runs:
                raise NotImplementedError(
                    "hash-key collision run exceeds the verified probe's "
                    f"candidate window ({VERIFY_CANDIDATES})"
                )
            unique_probe = self._make_unique_probe(use_dense)

            def make_full_unique():
                @jax.jit
                def probe_full_step(side, payload: Batch, flags, batch: Batch,
                                    params=()):
                    trace_probe()
                    with param_scope(params):
                        return body(side, payload, flags, batch)

                def body(side, payload: Batch, flags, batch: Batch):
                    res = unique_probe(side, payload, batch)
                    matched = res.matched
                    cols = dict(batch.columns)
                    for bo in outs:
                        src = payload[bo.source]
                        data = gather_rows(src.data, res.build_row, 0)
                        valid = gather_padded(src.valid, res.build_row, False)
                        cols[bo.name] = Column(data, valid & matched,
                                               src.dtype, src.dictionary)
                    # miss rows carry build_row == capacity -> dropped; a
                    # hash collision is a miss, so gate the scatter on the
                    # verified mask
                    cap = payload.capacity
                    rows = jnp.where(matched, res.build_row, cap)
                    flags = flags.at[rows].set(True, mode="drop")
                    return Batch(cols, batch.live), flags

                return probe_full_step

            self._full_step = EXEC_CACHE.get_or_build(
                EXEC_CACHE.key_of("lookup_full_unique", key, outs, verify,
                                  use_dense, pack_bits),
                make_full_unique,
            )
            return

        out_cap = self.out_capacity
        assert out_cap is not None, "expansion join requires out_capacity"
        assert not verify, (
            "hash-key verification on expansion FULL OUTER is unsupported "
            "(an all-collision probe row cannot re-synthesize its "
            "null-extended output row)"
        )

        def make_full_expand():
            @jax.jit
            def probe_full_step(side: BuildSide, payload: Batch, flags,
                                batch: Batch, params=()):
                trace_probe()
                with param_scope(params):
                    return body(side, payload, flags, batch)

            def body(side: BuildSide, payload: Batch, flags, batch: Batch):
                v = evaluate(key, batch)
                res = probe_expand(side, v.data, batch.live & v.valid, out_cap,
                                   left=True, emit_live=batch.live)
                cols = {}
                for name in batch.names:
                    src = batch[name]
                    cols[name] = Column(
                        gather_rows(src.data, res.probe_row, 0),
                        gather_padded(src.valid, res.probe_row, False),
                        src.dtype,
                        src.dictionary,
                    )
                for bo in outs:
                    src = payload[bo.source]
                    cols[bo.name] = Column(
                        gather_rows(src.data, res.build_row, 0),
                        gather_padded(src.valid, res.build_row, False),
                        src.dtype,
                        src.dictionary,
                    )
                flags = flags.at[res.build_row].set(True, mode="drop")
                return Batch(cols, res.live), flags, res.overflow

            return probe_full_step

        self._full_step = EXEC_CACHE.get_or_build(
            EXEC_CACHE.key_of("lookup_full_expand", key, outs, out_cap),
            make_full_expand,
        )

    def process_full(self, batch: Batch, flags):
        """One FULL OUTER probe step: returns (out_batch, new_flags).
        Raises CapacityOverflow on expansion overflow — the caller
        retries the same batch with the PREVIOUS flags."""
        assert self.build.build_side is not None, "build side not finished"
        self._check_probe_dict(batch)
        self._ensure_full_step()
        if self.unique:
            side = (
                self.build.dense_side
                if self.build.dense_side is not None
                else self.build.build_side
            )
            with trace_span("step:probe_full", "step"):
                return self._full_step(side, self.build.payload, flags, batch,
                                       self._params)
        with trace_span("step:probe_full", "step"):
            out, new_flags, overflow = self._full_step(
                self.build.build_side, self.build.payload, flags, batch,
                self._params,
            )
        with trace_sync("probe_overflow"):
            overflow = bool(overflow)
        if overflow:
            raise CapacityOverflow("LookupJoin", self.out_capacity)
        return out, new_flags


def verified_unique_probe(side, key, verify, payload: Batch, batch: Batch):
    """Unique probe over hashed keys with in-kernel verification.

    Distinct build values can collide on one hashed key, making the
    hashed key non-unique even though the original build keys are
    unique — the position search alone would return one arbitrary colliding
    candidate and the bytes check would then wrongly reject the true
    match, silently dropping join rows. So scan the whole collision
    run (VERIFY_CANDIDATES wide; builds refuse longer runs via
    ``long_dup_runs``) and keep the value-verified candidate. Shared
    by LookupJoinOperator and the distributed repartition-join step."""
    v = evaluate(key, batch)
    plive = batch.live & v.valid
    pk = jnp.where(plive, v.data.astype(jnp.int64), _I64_SENTINEL)
    lo = sorted_positions(side.sorted_keys, pk)
    cap = side.row_idx.shape[0]
    best = jnp.full(pk.shape, cap, side.row_idx.dtype)
    matched = jnp.zeros(pk.shape, jnp.bool_)
    for k in range(VERIFY_CANDIDATES):
        pos = lo + k
        hit = gather_padded(side.sorted_keys, pos, _I64_SENTINEL)
        row = gather_padded(side.row_idx, pos, cap)
        ok = (hit == pk) & plive & (pk != _I64_SENTINEL)
        ok = verify_mask(verify, batch, payload, row, init=ok)
        take = ok & ~matched
        best = jnp.where(take, row, best)
        matched = matched | ok
    return UniqueProbe(jnp.where(matched, best, cap), matched)


def full_init_flags(build: JoinBuildOperator):
    """Fresh matched-build flags for a FULL OUTER probe pass."""
    return jnp.zeros(build.payload.capacity, dtype=bool)


def full_tail_batch(
    payload: Batch,
    build_outputs: Sequence[BuildOutput],
    flags,
    probe_schema: Batch,
) -> Batch:
    """Unmatched ``payload`` rows (live & ~flags) with NULL probe
    columns. ``probe_schema`` supplies probe-side names/dtypes/
    dictionaries (any probe batch). The ONE tail constructor behind
    both FULL OUTER paths: called eagerly by the local/broadcast tiers
    and traced inside the distributed repartition step — the two must
    never diverge on tail semantics."""
    cap = payload.capacity
    out_names = {bo.name for bo in build_outputs}
    cols = {}
    for name in probe_schema.names:
        if name in out_names:
            continue
        src = probe_schema[name]
        cols[name] = Column(
            jnp.zeros((cap,) + src.data.shape[1:], src.data.dtype),
            jnp.zeros(cap, dtype=bool),
            src.dtype,
            src.dictionary,
        )
    for bo in build_outputs:
        src = payload[bo.source]
        cols[bo.name] = Column(src.data, src.valid, src.dtype, src.dictionary)
    return Batch(cols, payload.live & ~flags)


def full_tail(
    build: JoinBuildOperator,
    build_outputs: Sequence[BuildOutput],
    flags,
    probe_schema: Batch,
) -> Batch:
    """Eager wrapper over ``full_tail_batch`` for operator-held builds
    (runs once per query)."""
    return full_tail_batch(build.payload, build_outputs, flags, probe_schema)
