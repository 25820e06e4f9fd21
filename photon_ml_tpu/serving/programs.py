"""AOT fixed-shape scoring programs over a model bank.

Per-request latency on XLA is only predictable when nothing in the
request path can trigger a compile (the pjit/TPUv4 discipline: a small
closed set of shapes, all lowered ahead of time). The request path here
sees exactly ``len(ladder)`` program shapes per model signature — one
padded batch shape per ladder rung — and every one of them is
``lower().compile()``d at bank-load/swap-stage time, BEFORE the shape
can appear on the hot path. After warmup the dispatch loop only ever
calls precompiled executables; the zero-recompile contract is pinned by
``tests/test_serving.py`` with jax's lowering counter.

The executable cache is keyed like the tile-schedule cache: by content
signature — ``(bank spec, padded batch shape)`` — not by bank object
identity, so a hot-swapped generation with unchanged shapes reuses every
program, and a re-load of the same model costs zero compiles.

The scoring function replays the batch scorer's per-coordinate algebra
(`game.model_io.LoadedGameModel.score`) term for term — same gathers,
same per-row reductions, same accumulation order — which is what makes
serving scores bitwise-equal to the batch driver's.
"""

from __future__ import annotations

import threading
from typing import Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

from photon_ml_tpu.serving.model_bank import ModelBank

__all__ = [
    "RequestBatch",
    "ServingPrograms",
    "DEFAULT_LADDER",
    "select_shape",
    "term_entries",
]

# Padded micro-batch shapes, smallest to largest. 1 serves the idle
# closed loop with no pad waste; 256 is the saturating-load coalescing
# cap (past ~256 rows the per-dispatch fixed cost is already amortized
# to noise and bigger shapes only add tail latency).
DEFAULT_LADDER = (1, 8, 64, 256)


def select_shape(n: int, ladder: Sequence[int]) -> int:
    """Smallest ladder shape that fits ``n`` rows (callers cap takes at
    ``max(ladder)``, so there is always one)."""
    for b in ladder:
        if n <= b:
            return b
    raise ValueError(f"batch of {n} exceeds the ladder {tuple(ladder)}")


class RequestBatch(NamedTuple):
    """One padded micro-batch: per-shard features, per-id-type entity
    codes, offsets. Padded rows carry zero features, code -1 and offset
    0 — they score finite garbage that the demux discards."""

    indices: Dict[str, jnp.ndarray]  # shard -> int32 [B, k]
    values: Dict[str, jnp.ndarray]  # shard -> float32 [B, k]
    codes: Dict[str, jnp.ndarray]  # re/mf id type -> int32 [B]
    offsets: jnp.ndarray  # float32 [B]


def _score_spec(spec, arrays, batch: RequestBatch):
    """Margins + offsets for one padded batch. ``spec`` is static (the
    bank signature); the loop unrolls at trace time into the exact
    coordinate-order sum the batch scorer computes eagerly."""
    total = jnp.zeros(batch.offsets.shape, jnp.float32)
    for entry in spec:
        kind, name = entry[0], entry[1]
        if kind == "fe":
            shard_id = entry[2]
            w = arrays[name]
            total = total + jnp.sum(
                batch.values[shard_id]
                * jnp.take(w, batch.indices[shard_id], axis=0),
                axis=-1,
            )
        elif kind == "re":
            re_type, shard_id = entry[2], entry[3]
            bank = arrays[name]
            codes = batch.codes[re_type]
            valid = codes >= 0
            w_rows = jnp.take(bank, jnp.maximum(codes, 0), axis=0)
            score = jnp.sum(
                batch.values[shard_id]
                * jnp.take_along_axis(
                    w_rows, batch.indices[shard_id], axis=1
                ),
                axis=-1,
            )
            total = total + jnp.where(valid, score, 0.0)
        else:  # mf
            row_t, col_t = entry[2], entry[3]
            R, C = arrays[name]
            rows = batch.codes[row_t]
            cols = batch.codes[col_t]
            valid = (rows >= 0) & (cols >= 0)
            r = jnp.take(R, jnp.maximum(rows, 0), axis=0)
            c = jnp.take(C, jnp.maximum(cols, 0), axis=0)
            total = total + jnp.where(valid, jnp.sum(r * c, axis=-1), 0.0)
    return total + batch.offsets


def term_entries(spec):
    """The ordered (kind, name, id_types, feature_shard) of every
    per-entity spec entry — the coordinate slots a
    :class:`~.admission.PartialScore` carries and the routing tier
    re-sums. MF entries list both id types and no feature shard (their
    term is a latent dot product). Order IS the contract: the router
    adds terms in exactly this sequence, which is the full program's
    accumulation order."""
    out = []
    for entry in spec:
        if entry[0] == "re":
            out.append(("re", entry[1], (entry[2],), entry[3]))
        elif entry[0] == "mf":
            out.append(("mf", entry[1], (entry[2], entry[3]), None))
    return tuple(out)


def _score_spec_partial(spec, arrays, batch: RequestBatch):
    """The scatter/gather decomposition of :func:`_score_spec`: the
    fixed-effect accumulation (identical chain of f32 adds as the full
    program's FE prefix — every shard holds the full FE banks) and one
    column per re/mf entry with that coordinate's term (0.0 where the
    entity code is -1, exactly the zero the full program adds). The
    router recomposes ``((fe + t_1) + t_2)… + offset`` host-side in
    float32 — each step exactly-rounded IEEE, so the routed margin is
    bitwise the single-server one. Offsets are NOT added here: the
    router owns them (it has the request; sub-requests may fan out to
    several shards and the offset must be applied exactly once)."""
    fe = jnp.zeros(batch.offsets.shape, jnp.float32)
    terms = []
    for entry in spec:
        kind, name = entry[0], entry[1]
        if kind == "fe":
            shard_id = entry[2]
            w = arrays[name]
            fe = fe + jnp.sum(
                batch.values[shard_id]
                * jnp.take(w, batch.indices[shard_id], axis=0),
                axis=-1,
            )
        elif kind == "re":
            re_type, shard_id = entry[2], entry[3]
            bank = arrays[name]
            codes = batch.codes[re_type]
            valid = codes >= 0
            w_rows = jnp.take(bank, jnp.maximum(codes, 0), axis=0)
            score = jnp.sum(
                batch.values[shard_id]
                * jnp.take_along_axis(
                    w_rows, batch.indices[shard_id], axis=1
                ),
                axis=-1,
            )
            terms.append(jnp.where(valid, score, 0.0))
        else:  # mf
            row_t, col_t = entry[2], entry[3]
            R, C = arrays[name]
            rows = batch.codes[row_t]
            cols = batch.codes[col_t]
            valid = (rows >= 0) & (cols >= 0)
            r = jnp.take(R, jnp.maximum(rows, 0), axis=0)
            c = jnp.take(C, jnp.maximum(cols, 0), axis=0)
            terms.append(jnp.where(valid, jnp.sum(r * c, axis=-1), 0.0))
    stacked = (
        jnp.stack(terms, axis=1)
        if terms
        else jnp.zeros(batch.offsets.shape + (0,), jnp.float32)
    )
    return fe, stacked


# photon: sharding(axes=[])
_score_jit = jax.jit(_score_spec, static_argnums=(0,))
# photon: sharding(axes=[])
_score_partial_jit = jax.jit(_score_spec_partial, static_argnums=(0,))


def _batch_structs(spec, B: int) -> RequestBatch:
    """ShapeDtypeStructs of a padded batch at ladder shape ``B`` (the
    lowering inputs; shard widths/id types come from the spec)."""
    f32, i32 = jnp.float32, jnp.int32
    indices: Dict[str, jax.ShapeDtypeStruct] = {}
    values: Dict[str, jax.ShapeDtypeStruct] = {}
    codes: Dict[str, jax.ShapeDtypeStruct] = {}
    for entry in spec:
        kind = entry[0]
        if kind == "fe":
            shard_id, _d, k = entry[2], entry[3], entry[4]
            indices[shard_id] = jax.ShapeDtypeStruct((B, k), i32)
            values[shard_id] = jax.ShapeDtypeStruct((B, k), f32)
        elif kind == "re":
            re_type, shard_id, k = entry[2], entry[3], entry[6]
            indices[shard_id] = jax.ShapeDtypeStruct((B, k), i32)
            values[shard_id] = jax.ShapeDtypeStruct((B, k), f32)
            codes[re_type] = jax.ShapeDtypeStruct((B,), i32)
        else:
            for t in (entry[2], entry[3]):
                codes[t] = jax.ShapeDtypeStruct((B,), i32)
    return RequestBatch(
        indices=indices,
        values=values,
        codes=codes,
        offsets=jax.ShapeDtypeStruct((B,), f32),
    )


def _array_structs(arrays):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), arrays
    )


class ServingPrograms:
    """The per-shape executable cache. ``ensure_compiled`` is the warmup
    seam (bank load + swap staging); ``score`` is the hot path and — by
    contract — never lowers anything the cache does not already hold
    unless an unwarmed shape arrives (counted, and zero after warmup)."""

    def __init__(self, ladder: Sequence[int] = DEFAULT_LADDER, max_entries: int = 64):
        if not ladder or list(ladder) != sorted(set(int(b) for b in ladder)):
            raise ValueError(
                f"ladder must be strictly increasing and non-empty: {ladder}"
            )
        self.ladder: Tuple[int, ...] = tuple(int(b) for b in ladder)
        self._max_entries = max_entries
        self._lock = threading.Lock()
        # insertion-ordered dict used as an LRU: every hit re-inserts at
        # the end, so eviction (front pop) drops the coldest entry and
        # spec churn can never push out the live bank's ladder rungs
        self._cache: Dict[tuple, object] = {}
        # single-flight guard: key -> Event held by the thread compiling
        # it, so racing callers wait instead of compiling redundantly
        self._inflight: Dict[tuple, threading.Event] = {}
        self.compile_count = 0
        self.cold_dispatch_compiles = 0

    def _lru_get(self, key):  # photon: guarded-by(_lock)
        """Cache lookup + recency touch. Caller holds ``self._lock``
        (declared on the def line; the analyzer checks call sites)."""
        exe = self._cache.get(key)
        if exe is not None:
            self._cache[key] = self._cache.pop(key)
        return exe

    def _get_or_compile(self, spec, arrays, B: int, *,
                        partial: bool = False):
        """Returns ``(executable, freshly_compiled)``. Exactly one
        thread lowers a given (spec, B, mode); losers of the race wait
        on the winner's event and take the cached result. If the
        winner's compile raises, waiters retry (and may compile
        themselves). ``partial`` selects the scatter/gather program
        (fe + per-coordinate terms) over the full-margin one — the two
        families share the LRU, keyed apart."""
        key = (spec, B, bool(partial))
        while True:
            with self._lock:
                exe = self._lru_get(key)
                if exe is not None:
                    return exe, False
                ev = self._inflight.get(key)
                if ev is None:
                    ev = threading.Event()
                    self._inflight[key] = ev
                    break
            # timed wait (request-path hygiene, PL007): re-check the
            # cache each beat instead of parking unbounded on the
            # winner's event
            while not ev.wait(timeout=0.1):
                continue
        try:
            jitted = _score_partial_jit if partial else _score_jit
            exe = jitted.lower(
                spec, _array_structs(arrays), _batch_structs(spec, B)
            ).compile()
            with self._lock:
                while len(self._cache) >= self._max_entries:
                    self._cache.pop(next(iter(self._cache)))
                self._cache[key] = exe
                self.compile_count += 1
            return exe, True
        finally:
            with self._lock:
                self._inflight.pop(key, None)
            ev.set()

    def ensure_compiled(self, bank: ModelBank, *,
                        partial: bool = False) -> int:
        """AOT-compile every ladder shape for this bank's signature;
        returns how many programs were newly compiled (0 when the spec
        was already warm — the swap-without-recompile case).
        ``partial`` warms the shard-server program family instead of
        the full-margin one."""
        fresh = 0
        for B in self.ladder:
            _, new = self._get_or_compile(
                bank.spec, bank.arrays, B, partial=partial
            )
            fresh += int(new)
        return fresh

    def executable(self, spec, B: int, *, partial: bool = False):
        with self._lock:
            return self._lru_get((spec, B, bool(partial)))

    def score(self, bank: ModelBank, batch: RequestBatch) -> jnp.ndarray:
        """Device scores for one padded batch (no readback here — the
        batcher owns the single counted device_get per dispatch)."""
        B = batch.offsets.shape[0]
        exe = self.executable(bank.spec, B)
        if exe is None:
            # an unwarmed shape reached the hot path: compile it now and
            # count the miss — the tests pin this at zero after warmup
            with self._lock:
                self.cold_dispatch_compiles += 1
            exe, _ = self._get_or_compile(bank.spec, bank.arrays, B)
        return exe(bank.arrays, batch)

    def score_partial(self, bank: ModelBank, batch: RequestBatch):
        """Device (fe[B], terms[B, R]) for one padded batch — the
        shard-server half of a routed score. Same zero-recompile
        contract as :meth:`score` (shard servers warm this family at
        load/swap-stage time); no readback here either."""
        B = batch.offsets.shape[0]
        exe = self.executable(bank.spec, B, partial=True)
        if exe is None:
            with self._lock:
                self.cold_dispatch_compiles += 1
            exe, _ = self._get_or_compile(
                bank.spec, bank.arrays, B, partial=True
            )
        return exe(bank.arrays, batch)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "compiled_programs": len(self._cache),
                "compile_count": self.compile_count,
                "cold_dispatch_compiles": self.cold_dispatch_compiles,
            }
