"""Distributed semi-naive materialisation under ``shard_map``.

The paper's engine is single-node.  To make the technique deployable at
cluster scale we add the standard distributed-datalog construction
(hash-partition + dynamic data exchange, after Ajileye, Motik & Horrocks
arXiv 2001.10206), mapped onto JAX-native collectives:

* every relation is **hash-partitioned on its first argument** across the
  ``data`` axis of the device mesh;
* each shard keeps ``old``/``delta`` partitions per predicate (mirroring
  :class:`~repro.core.metafacts.FactStore`'s semi-naive bookkeeping): a
  padded row buffer plus a count and a delta watermark — rows in
  ``[lo, count)`` are the last round's delta, rows in ``[0, lo)`` are old;
* each round evaluates one compiled ``(rule, pivot)`` plan per delta
  pivot — plans come from the shared body compiler
  (:mod:`repro.core.compile`), which also picks the **exchange key**: a
  join side whose stored first column already is the planned join
  variable skips its pre-join ``all_to_all`` entirely;
* derivations whose head key hashes to another shard are exchanged with
  one ``all_to_all`` per head predicate per round (skipped too when the
  planner proves every emitted row is already on its owner shard);
* the fixpoint runs stratum-by-stratum over the SCC condensation
  (:mod:`repro.core.program_graph`); ``(rule, pivot)`` pairs whose pivot
  predicate received no delta are skipped on the host without tracing
  (``rule_applications_skipped``, as in the host engines);
* per-shard exchange capacity **grows on overflow** (the round is retried
  with doubled padding, counted in ``exchange_regrows``) instead of
  aborting the fixpoint.

Beyond materialisation the engine is *incrementally maintainable*:
:meth:`DistributedEngine.apply` routes overdelete / rederive / insert
batches through the same ``all_to_all`` exchange, mirroring the DRed
phases of :mod:`repro.incremental.dred` set-at-a-time over the shards,
and :meth:`DistributedEngine.check_integrity` differentially compares
the result against a host :class:`~repro.incremental.IncrementalStore`.

Facts live in fixed-capacity padded buffers (JAX static shapes): a
``(capacity, arity)`` int32 array plus validity counts; empty slots hold
``EMPTY = -1``.  Join/dedup primitives are the jnp twins of the numpy
host path in :mod:`repro.core.util` and are what the Pallas kernels
accelerate.  Every sort is unstable (``stable=False``): no result
depends on the order of equal keys, and the TPU compiler builds an
unstable sort of 2^18 int32 keys several times faster than a stable
one — sort compilation dominates the cold compile of a round.  The
same code lowers on the 1-device CPU mesh (tests), the forced 4-device
CPU mesh (CI matrix), and the multi-pod mesh (dry-run).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..obs import instant, publish_distributed, span
from .compile import SRC_DELTA, SRC_OLD, PlanCache, compile_body, stats_bucket
from .datalog import Program
from .engine import MaterialisationStats
from .program_graph import stratify, stratum_predicates
from .util import unique_rows

EMPTY = jnp.int32(-1)

__all__ = ["DistributedEngine", "DistributedStats"]


@dataclass
class DistributedStats(MaterialisationStats):
    """Materialisation/maintenance statistics with the exchange-layer
    counters the host engines have no analogue for."""

    #: matching pairs enumerated by the local joins (the paper's "work")
    rows_joined: int = 0
    #: all_to_all calls issued (pre-join re-keying + head routing)
    exchanges: int = 0
    #: all_to_all calls avoided because the planner's partition key
    #: matched the storage sharding (or every head row was emitted on
    #: its owner shard)
    exchanges_skipped: int = 0
    #: rounds retried with doubled exchange/join padding after overflow
    exchange_regrows: int = 0
    # incremental maintenance (apply) counters, IncrementalStats-aligned
    epoch: int = 0
    n_del_explicit: int = 0
    n_add_explicit: int = 0
    n_overdeleted: int = 0
    n_rederived: int = 0
    n_deleted: int = 0
    n_inserted: int = 0
    #: device->host waits: each call of ``DistributedEngine._fetch``
    #: counts one, however many arrays it reads (a program's packed
    #: block, or every result buffer of a pull)
    host_syncs: int = 0
    #: int32 words of a binary fact's key in the programs that ran
    #: (:func:`key_words` of the largest id the engine has seen)
    key_words: int = 1


#: the psum'd scalars at the head of a round program's packed block
ROUND_SCALARS = ("total_new", "dropped", "overflow", "joined")
#: ... and of a merge program's
MERGE_SCALARS = ("total_new", "overflow")


def _packed_block(parts) -> jax.Array:
    """A program's packed block, one ``(1, K)`` int32 row a shard: its
    psum'd scalars, then each predicate's new count on the shard, in
    ``_preds`` order.  ``parts`` are ``(1,)`` arrays.  The host reads
    nothing else of a program, and reads it once."""
    return jnp.concatenate(parts)[None]


def _hash_shard(keys: jax.Array, n_shards: int) -> jax.Array:
    """Multiplicative hash -> shard id (stable across rounds)."""
    h = (keys.astype(jnp.uint32) * jnp.uint32(2654435761)) >> jnp.uint32(16)
    return (h % jnp.uint32(n_shards)).astype(jnp.int32)


def _hash_shard_np(keys: np.ndarray, n_shards: int) -> np.ndarray:
    """Host twin of :func:`_hash_shard` (batch routing, dataset loads)."""
    h = (keys.astype(np.uint32) * np.uint32(2654435761)) >> np.uint32(16)
    return (h % np.uint32(n_shards)).astype(np.int32)


# --------------------------------------------------------------------- #
# jnp primitives (device twins of core.util; kernels/ accelerates these)
# --------------------------------------------------------------------- #
def sorted_member_jnp(
    a: tuple, b_sorted: tuple, a_sorted: bool = False
) -> jax.Array:
    """Membership of each key of ``a`` in the sorted keys ``b_sorted``
    (BIG-padded at the end allowed); keys are tuples of int32 words
    (:func:`pack_pairs`).  ``a_sorted`` is accepted for parity with the
    kernel and unused."""
    from ..kernels.sorted_member import searchsorted_words

    idx = searchsorted_words(b_sorted, a)
    idx = jnp.minimum(idx, b_sorted[0].shape[0] - 1)
    hit = b_sorted[0][idx] == a[0]
    for b, x in zip(b_sorted[1:], a[1:]):
        hit = hit & (b[idx] == x)
    return hit


def sorted_member_kernel(
    a: tuple, b_sorted: tuple, a_sorted: bool = False
) -> jax.Array:
    """Pallas-kernel membership (``repro.kernels.sorted_member``) — the
    TPU device path for the dedup anti-join; two-word keys take the
    two-word kernel.  ``interpret`` is backend-detected (interpret on
    CPU, compiled on TPU — see ``repro.kernels.backend``)."""
    from ..kernels import ops

    if len(a) == 1:
        a, b_sorted = a[0], b_sorted[0]
    return ops.member(a, b_sorted, a_sorted=a_sorted, interpret=None)


#: x64 is disabled by default in JAX, so fact keys are int32 words.  A
#: binary fact's key is one word, both ids in 16-bit halves, while every
#: id the engine has seen is below ONE_WORD_IDS, and two words, the ids
#: themselves compared lexicographically, once one is not.  Either way
#: ids lie in [0, MAX_DIST_CONST), below BIG, the sentinel of every sort.
ONE_WORD_IDS = 1 << 15
MAX_DIST_CONST = int(np.iinfo(np.int32).max)
BIG = jnp.int32(np.iinfo(np.int32).max)


def key_words(max_id: int) -> int:
    """Words of a binary fact's key when no id exceeds ``max_id``."""
    return 1 if max_id < ONE_WORD_IDS else 2


def pack_pairs(rows: jax.Array, words: int = 1) -> tuple:
    """Sortable int32 keys of (n, arity) int32 rows, as a tuple of words:
    a unary row's id; a binary row's ids in 16-bit halves of one word, or
    with ``words=2`` the two ids as they are."""
    if rows.shape[1] == 1:
        return (rows[:, 0],)
    hi = rows[:, 0]
    lo = rows[:, 1]
    if words == 2:
        return (hi, lo)
    return ((hi << 16) | (lo & 0xFFFF),)


def sort_keys(keys: tuple, valid: jax.Array) -> tuple:
    """The valid keys sorted lexicographically, each word of the invalid
    ones BIG (so they sort last)."""
    masked = tuple(jnp.where(valid, w, BIG) for w in keys)
    if len(masked) == 1:  # jnp.sort: one-word programs keep their HLO
        return (jnp.sort(masked[0], stable=False),)
    return tuple(
        jax.lax.sort(masked, num_keys=len(masked), is_stable=False)
    )


def _neighbours_differ(ks: tuple) -> jax.Array:
    """``ks[i + 1] != ks[i]`` over keys of one or more words."""
    differs = ks[0][1:] != ks[0][:-1]
    for w in ks[1:]:
        differs = differs | (w[1:] != w[:-1])
    return differs


def dedup_against(
    new_keys: tuple, new_valid: jax.Array, old_keys_sorted: tuple,
    member_fn=sorted_member_jnp, restrict_sorted: tuple | None = None,
) -> jax.Array:
    """Valid-mask of new facts that are not already present in old (and,
    with ``restrict_sorted``, that are present there); keys are tuples
    of words (:func:`pack_pairs`).

    Works on the sorted keys — first occurrences are neighbour compares,
    and the membership probes arrive sorted — then scatters back."""
    masked = tuple(jnp.where(new_valid, w, BIG) for w in new_keys)
    *ks, order = jax.lax.sort(
        (*masked, jnp.arange(masked[0].shape[0], dtype=jnp.int32)),
        num_keys=len(masked), is_stable=False,
    )
    ks = tuple(ks)
    keep = jnp.concatenate([jnp.ones((1,), bool), _neighbours_differ(ks)])
    # valid keys never hold BIG in their first word (ids stay below it)
    keep = keep & (ks[0] != BIG) & ~member_fn(ks, old_keys_sorted, a_sorted=True)
    if restrict_sorted is not None:
        keep = keep & member_fn(ks, restrict_sorted, a_sorted=True)
    return jnp.zeros_like(keep).at[order].set(keep)


def join_on_key(
    l_keys: jax.Array,
    l_valid: jax.Array,
    l_payload: jax.Array,
    r_keys: jax.Array,
    r_valid: jax.Array,
    r_payload: jax.Array,
    out_capacity: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Equi-join with bounded output (static shapes).

    Returns ``(left payload, right payload, valid, total)`` for up to
    ``out_capacity`` matching pairs, enumerated as (left row) x (matching
    right rows); ``total`` is the true join size so the caller can detect
    truncation (and regrow) instead of silently under-deriving.
    """
    r_sort_key = jnp.where(r_valid, r_keys, BIG)
    order = jnp.argsort(r_sort_key, stable=False)
    r_keys_s = r_sort_key[order]
    r_payload_s = r_payload[order]

    lo = jnp.searchsorted(r_keys_s, jnp.where(l_valid, l_keys, BIG - 1), side="left")
    hi = jnp.searchsorted(r_keys_s, jnp.where(l_valid, l_keys, BIG - 1), side="right")
    counts = jnp.where(l_valid, hi - lo, 0)
    offsets = jnp.cumsum(counts) - counts
    total = jnp.sum(counts)

    out_idx = jnp.arange(out_capacity)
    # which left row does output slot i belong to?
    l_of = jnp.searchsorted(offsets + counts, out_idx, side="right")
    l_of = jnp.minimum(l_of, l_keys.shape[0] - 1)
    within = out_idx - offsets[l_of]
    r_of = jnp.minimum(lo[l_of] + within, r_keys.shape[0] - 1)
    valid = out_idx < total
    return l_payload[l_of], r_payload_s[r_of], valid, total


def _apply_atom_constraints(atom, rows, valid):
    """Constants / repeated variables as validity-mask filters."""
    vars_ = atom.variables()
    first = {v: atom.terms.index(v) for v in vars_}
    for pos, t in enumerate(atom.terms):
        if isinstance(t, int):
            valid = valid & (rows[:, pos] == t)
        elif pos != first[t]:
            valid = valid & (rows[:, pos] == rows[:, first[t]])
    cols = [rows[:, first[v]] for v in vars_]
    return jnp.stack(cols, axis=1), valid


def _project_head(body_vars, rows, head):
    cols = []
    for t in head.terms:
        if isinstance(t, int):
            cols.append(jnp.full((rows.shape[0],), t, dtype=rows.dtype))
        elif t in body_vars:
            cols.append(rows[:, body_vars.index(t)])
        else:
            return None
    return jnp.stack(cols, axis=1)


class _SchemaStats:
    """Planner statistics from host-tracked global row counts.

    Cardinalities are clamped ``>= 1`` (a delta/maintenance plan must
    never compile to the empty plan just because a partition is
    currently empty — real emptiness is a host-side scheduling decision,
    the same contract :class:`repro.incremental.eval.PhaseStats` keeps);
    arities come from the program/dataset schema."""

    def __init__(self, counts: dict[str, int], arities: dict[str, int]):
        self.counts = counts
        self.arities = arities

    def n_rows(self, pred: str) -> int:
        return max(int(self.counts.get(pred, 0)), 1)

    def arity(self, pred: str) -> int:
        return self.arities.get(pred, 0)

    def selectivity(self, pred: str, pos: int, value: int) -> float:
        return 1.0 / max(float(np.sqrt(self.n_rows(pred))), 1.0)


@dataclass
class _Variant:
    """One traced round function + its static exchange schedule."""

    fn: object
    n_exchanges: int
    n_exchanges_skipped: int


# --------------------------------------------------------------------- #
# the distributed engine
# --------------------------------------------------------------------- #
class DistributedEngine:
    """Hash-partitioned semi-naive materialisation for binary datalog.

    Supports the rule shapes that cover RDF/OWL-RL style programs after
    vertical partitioning (arity <= 2): single-atom rules and two-atom
    single-key joins ``A(x,y), B(y,z) -> H(x,z)`` (plus unary variants).
    The host drives rounds; each round is one jitted ``shard_map`` call.

    ``seminaive=False`` reproduces the legacy naive iteration (every
    rule re-joins its full relations each round) — the baseline the
    benchmarks compare against; ``planner_exchange_keys=False`` disables
    the alignment-based exchange elision.
    """

    def __init__(
        self,
        program: Program,
        mesh: Mesh,
        axis: str = "data",
        capacity: int = 1 << 14,
        join_capacity: int | None = None,
        use_pallas_kernels: bool = False,
        seminaive: bool = True,
        planner_exchange_keys: bool = True,
        max_regrows: int = 8,
    ):
        self.program = program
        self.mesh = mesh
        self.axis = axis
        self.capacity = capacity
        self.join_capacity = join_capacity or capacity
        self.n_shards = mesh.shape[axis]
        self.seminaive = seminaive
        self.planner_exchange_keys = planner_exchange_keys
        self.max_regrows = max_regrows
        # TPU device path: dedup membership through the Pallas kernel
        self._member_fn = (
            sorted_member_kernel if use_pallas_kernels else sorted_member_jnp
        )
        self._plan_cache = PlanCache()
        self._variants: dict = {}
        #: per-predicate sharded state: pred -> [rows, count, delta_lo]
        self._state: dict[str, list] | None = None
        #: host mirror of the state's per-shard counts and watermarks:
        #: pred -> (cnt, lo), int32 arrays of shape (n_shards,)
        self._mirror: dict[str, tuple[np.ndarray, np.ndarray]] = {}
        self._preds: tuple[str, ...] = ()
        self._arities: dict[str, int] = {}
        #: words of a binary fact's key (:func:`key_words`), from the
        #: largest id seen in facts and in the program's constants; part
        #: of every variant's key
        self._key_words = 1
        #: the largest constant the rules emit or match on device
        self._rule_top = max(
            (t for rule in program for atom in (rule.head, *rule.body)
             for t in atom.terms if isinstance(t, int)),
            default=0,
        )
        #: global row count per predicate (the mirror's cnt summed)
        self._counts: dict[str, int] = {}
        #: host-side explicit fact set (int64 rows; the apply() contract)
        self.explicit: dict[str, np.ndarray] = {}
        self.stats = DistributedStats()
        self.rounds = 0
        self.epoch = 0
        #: exchange/join padding multiplier, doubled on overflow retries
        self._factor = 1
        #: True while an apply() sweep is in flight: a mid-sweep failure
        #: leaves shards and the explicit set inconsistent, so further
        #: applies are refused until the next materialise()
        self._dirty = False
        # provenance (obs.provenance): rule ids are program positions —
        # the id namespace shared with the host engines and the journal
        self._rule_ids: dict = {}
        for k, rule in enumerate(program):
            self._rule_ids.setdefault(rule, k)
        self._pjournal = None  # bound per-materialise/apply when enabled

    def _record_dist(
        self,
        kind: str,
        pred: str,
        *,
        stratum: int = -1,
        round_no: int = 0,
        rule_id: int = -1,
        pivot: int = -1,
        n_new: int = 0,
        shard: int = -1,
    ) -> None:
        """Journal one host-visible distributed event (no-op when
        recording is off).  Per-shard growth records carry the shard tag
        and are coalesced by ``journal.merge_shard_records()`` at
        differential verify; per-(rule, pivot) schedule records carry
        the rule lineage (device kernels do not expose per-rule emit
        counts, so counts live on the shard records)."""
        j = self._pjournal
        if j is None:
            return
        from ..obs.provenance import DerivationRecord

        j.record(DerivationRecord(
            kind=kind,
            engine="dist",
            stratum=stratum,
            round=round_no,
            rule_id=rule_id,
            pivot=pivot,
            pred=pred,
            n_new=int(n_new),
            shard=int(shard),
            epoch=j.epoch,
        ))

    # -------------------------------------------------------------- #
    # sharding / routing
    # -------------------------------------------------------------- #
    def _route(self, rows_by_pred: dict[str, np.ndarray]) -> dict:
        """Hash-partition host rows on their first column into per-shard
        padded buffers ``(n_shards, capacity, arity)`` + counts."""
        n, cap = self.n_shards, self.capacity
        out = {}
        for pred, rows in rows_by_pred.items():
            rows = np.asarray(rows)
            if rows.ndim == 1:
                rows = rows.reshape(-1, 1)
            self._check_const_range(pred, rows)
            rows = rows.astype(np.int32)
            arity = rows.shape[1]
            shard = _hash_shard_np(rows[:, 0], n)
            buf = np.full((n, cap, arity), -1, dtype=np.int32)
            cnt = np.zeros((n,), dtype=np.int32)
            for s in range(n):
                mine = rows[shard == s]
                if mine.shape[0] > cap:
                    raise ValueError(f"capacity {cap} too small for shard {s}")
                buf[s, : mine.shape[0]] = mine
                cnt[s] = mine.shape[0]
            out[pred] = (buf, cnt)
        return out

    def _to_shards(self, x: np.ndarray) -> jax.Array:
        """Place a host array whose leading axis is the shard axis onto
        the mesh: each device receives its own slice, nothing is staged
        on the first device."""
        spec = P(self.axis, *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    def _sharded_pair(self, routed: dict, pred: str) -> tuple:
        """``(rows, cnt)`` on the mesh for ``pred`` — empty buffers when
        the routed batch holds no rows of it."""
        buf, cnt = routed.get(pred) or (
            np.full((self.n_shards, self.capacity, self._arities[pred]), -1,
                    np.int32),
            np.zeros((self.n_shards,), np.int32),
        )
        return self._to_shards(buf), self._to_shards(cnt)

    @staticmethod
    def _check_const_range(pred: str, rows: np.ndarray) -> None:
        """Load-bearing for the BIG sentinel and the int32 state: an id
        outside ``[0, MAX_DIST_CONST)`` would silently corrupt keys."""
        if rows.size and (
            int(rows.min()) < 0 or int(rows.max()) >= MAX_DIST_CONST
        ):
            raise ValueError(
                f"distributed engine requires constants in "
                f"[0, {MAX_DIST_CONST}) — {pred!r} has values in "
                f"[{int(rows.min())}, {int(rows.max())}]"
            )

    def _see_ids(self, *batches) -> None:
        """Widen the keys to two words once an id of ``batches`` (dicts
        of range-checked rows) needs them; never narrows them."""
        top = max(
            (int(np.asarray(r).max()) for batch in batches
             for r in batch.values() if np.asarray(r).size),
            default=0,
        )
        self._key_words = max(self._key_words, key_words(top))

    def _flat_state(self) -> list:
        out = []
        for p in self._preds:
            out.extend(self._state[p])
        return out

    def _fetch(self, x):
        """Read a device array to the host, or a sequence of them as a
        list (every copy starts before the first is waited on).  Every
        device->host read of the engine goes through here; each call is
        one host wait, counted in ``stats.host_syncs``."""
        self.stats.host_syncs += 1
        if isinstance(x, (list, tuple)):
            for a in x:
                a.copy_to_host_async()
            return [np.asarray(a) for a in x]
        return np.asarray(x)

    def _set_mirror(self, pred: str, cnt: np.ndarray, lo: np.ndarray) -> None:
        self._mirror[pred] = (cnt, lo)
        self._counts[pred] = int(cnt.sum())

    def _delta_count(self, pred: str) -> int:
        cnt, lo = self._mirror[pred]
        return int((cnt - lo).sum())

    def _any_delta(self, preds) -> bool:
        """True when some predicate of ``preds`` holds a delta."""
        return any(self._delta_count(p) > 0 for p in preds if p in self._mirror)

    # -------------------------------------------------------------- #
    # planning
    # -------------------------------------------------------------- #
    def _plan(self, rule, pivot, frozen: bool = False):
        """Compile (rule, pivot) through the shared body compiler.

        ``frozen`` plans (the apply sweeps) are compiled once and never
        re-planned: a cardinality drift that flips the greedy anchor
        would change the plan signature and force a fresh XLA trace,
        which costs far more than the slightly stale join order."""
        sv = _SchemaStats(self._counts, self._arities)
        if frozen:
            plan = self._plan_cache.get(
                (rule, pivot, "frozen"),
                (0,),
                lambda: compile_body(rule.body, sv, pivot=pivot),
            )
        else:
            plan = self._plan_cache.get(
                (rule, pivot),
                stats_bucket(sv, rule.body),
                lambda: compile_body(rule.body, sv, pivot=pivot),
            )
        self._check_supported(rule, plan)
        return plan

    @staticmethod
    def supports_rule(rule) -> bool:
        """True iff the rule is in the engine's fragment: <= 2-atom body,
        and a two-atom body joins on exactly one shared variable.  The
        single place callers (serve, benches, tests) filter programs —
        keep in sync with :meth:`_check_supported`."""
        if len(rule.body) > 2:
            return False
        if len(rule.body) == 2:
            common = set(rule.body[0].variables()) & set(
                rule.body[1].variables()
            )
            if len(common) != 1:
                return False
        return True

    @classmethod
    def supported_program(cls, program: Program) -> Program:
        """The sub-program inside the distributed fragment."""
        return type(program)([r for r in program if cls.supports_rule(r)])

    @staticmethod
    def _check_supported(rule, plan) -> None:
        if len(rule.body) > 2:
            raise NotImplementedError(
                "distributed engine supports bodies of <= 2 atoms"
            )
        if plan.is_empty:
            raise AssertionError("schema stats must never compile empty plans")
        if plan.joins and (
            len(plan.joins[0].key_vars) != 1
            or plan.joins[0].partition_key is None
        ):
            raise NotImplementedError(
                "distributed engine supports single-key two-atom joins"
            )
        for atom in (rule.head, *rule.body):
            for t in atom.terms:
                # rule constants are emitted on device (jnp.full) and
                # never pass through _route's range guard — check here
                if isinstance(t, int) and not 0 <= t < MAX_DIST_CONST:
                    raise ValueError(
                        f"distributed engine requires constants in "
                        f"[0, {MAX_DIST_CONST}); rule {rule} uses {t}"
                    )

    def _resolve(self, rule_pivots, frozen: bool = False) -> tuple:
        return tuple(
            (rule, pivot, self._plan(rule, pivot, frozen=frozen))
            for rule, pivot in rule_pivots
        )

    # -------------------------------------------------------------- #
    # the exchange (one all_to_all; padding grows with self._factor)
    # -------------------------------------------------------------- #
    def _exchange(self, rows, valid, factor, keys=None):
        """Route rows to ``hash(key)`` owner shards with one all_to_all.

        ``keys`` defaults to the first column (relation-ownership routing
        for derived facts); joins pass the planned partition-key column
        so both sides are co-partitioned before the local merge.  Returns
        ``(rows, valid, n_dropped)``: rows past the per-bucket capacity
        are dropped and *counted* so the host can regrow the padding and
        retry the round instead of silently under-deriving."""
        if keys is None:
            keys = rows[:, 0]
        n_shards = self.n_shards
        if n_shards == 1:
            return rows, valid, jnp.zeros((), jnp.int32)
        rows = jnp.where(valid[:, None], rows, EMPTY)
        cap = rows.shape[0]
        # bucket capacity grows linearly with the regrow factor but never
        # past the input size — once a single bucket can hold every row,
        # no skew pattern can drop, so the regrow loop always terminates
        # (and buffers stay bounded by n_shards x input)
        per = min(max((cap * factor) // n_shards, 1), cap)
        shard_of = jnp.where(valid, _hash_shard(keys, n_shards), n_shards)
        # sort by destination (order inside a bucket is free: a dropped row
        # forces a retry); bucket i occupies slots [i*per,(i+1)*per)
        order = jnp.argsort(shard_of, stable=False)
        rows_s = rows[order]
        shard_s = shard_of[order]
        idx = jnp.arange(cap)
        # position within bucket (prefix count of same destination)
        pos_in_bucket = idx - jnp.searchsorted(shard_s, shard_s, side="left")
        ok = (pos_in_bucket < per) & (shard_s < n_shards)
        dropped = jnp.sum(((~ok) & (shard_s < n_shards)).astype(jnp.int32))
        slot = jnp.where(ok, shard_s * per + pos_in_bucket, n_shards * per)
        buckets = jnp.full(
            (n_shards * per + 1, rows.shape[1]), EMPTY, dtype=rows.dtype
        )
        buckets = buckets.at[slot].set(
            jnp.where(ok[:, None], rows_s, EMPTY)
        )[: n_shards * per]
        buckets = buckets.reshape(n_shards, per, rows.shape[1])
        exchanged = jax.lax.all_to_all(
            buckets, self.axis, split_axis=0, concat_axis=0, tiled=False
        )
        exchanged = exchanged.reshape(n_shards * per, rows.shape[1])
        valid_out = exchanged[:, 0] != EMPTY
        return exchanged, valid_out, dropped

    def _side_aligned(self, atom, key) -> bool:
        """True when a join side's stored partitioning (hash of the first
        term) already equals the planner's partition key — no exchange."""
        return bool(atom.terms) and atom.terms[0] == key

    # -------------------------------------------------------------- #
    # one (rule, pivot) plan, traced into a round
    # -------------------------------------------------------------- #
    def _trace_pair(self, rule, plan, part, emit, factor):
        """Trace one compiled (rule, pivot) body over the shard-local
        partitions; returns (dropped, rows_joined) tracers."""
        head = rule.head
        zero = jnp.zeros((), jnp.int32)
        steps = [plan.first] + [j.scan for j in plan.joins]
        if len(steps) == 1:
            st = steps[0]
            rows, valid = part(st.atom.predicate, st.source)
            rows, valid = _apply_atom_constraints(st.atom, rows, valid)
            out = _project_head(st.atom.variables(), rows, head)
            if out is not None:
                emit(head.predicate, out, valid,
                     head.terms[0] == st.atom.terms[0])
            return zero, zero

        a_step, b_step = steps
        key = plan.joins[0].partition_key
        dropped = zero
        sides = []
        for step in (a_step, b_step):
            rows, valid = part(step.atom.predicate, step.source)
            rows, valid = _apply_atom_constraints(step.atom, rows, valid)
            vars_ = step.atom.variables()
            # re-partition on the planned join key — unless this side's
            # storage sharding already is the key (planner-chosen
            # exchange keys: the annotation on JoinStep.partition_key)
            if self.n_shards > 1 and not (
                self.planner_exchange_keys and self._side_aligned(step.atom, key)
            ):
                with jax.named_scope("exchange"):
                    rows, valid, d = self._exchange(
                        rows, valid, factor, keys=rows[:, vars_.index(key)]
                    )
                dropped = dropped + d
            sides.append((rows, valid, vars_))
        (ra, va, va_vars), (rb, vb, vb_vars) = sides
        ka = ra[:, va_vars.index(key)]
        kb = rb[:, vb_vars.index(key)]
        jcap = self.join_capacity * factor
        lpay, rpay, valid, total = join_on_key(ka, va, ra, kb, vb, rb, jcap)
        dropped = dropped + jnp.maximum(total - jcap, 0).astype(jnp.int32)
        var_cols = {v: lpay[:, i] for i, v in enumerate(va_vars)}
        for i, v in enumerate(vb_vars):
            var_cols.setdefault(v, rpay[:, i])
        cols = [
            jnp.full((jcap,), t, jnp.int32) if isinstance(t, int)
            else var_cols[t]
            for t in head.terms
        ]
        emit(head.predicate, jnp.stack(cols, axis=1), valid,
             head.terms[0] == key)
        return dropped, total.astype(jnp.int32)

    def _static_exchange_counts(self, pairs) -> tuple[int, int]:
        """Host mirror of the trace's static exchange decisions: how many
        all_to_all calls one round issues, and how many the planner's
        partition keys elide."""
        if self.n_shards == 1:
            return 0, 0
        n_ex = n_sk = 0
        head_aligned: dict[str, bool] = {}
        for rule, _pivot, plan in pairs:
            steps = [plan.first] + [j.scan for j in plan.joins]
            if len(steps) == 2:
                key = plan.joins[0].partition_key
                for st in steps:
                    if self.planner_exchange_keys and self._side_aligned(
                        st.atom, key
                    ):
                        n_sk += 1
                    else:
                        n_ex += 1
                al = rule.head.terms[0] == key
            else:
                al = rule.head.terms[0] == steps[0].atom.terms[0]
            p = rule.head.predicate
            head_aligned[p] = head_aligned.get(p, True) and al
        for al in head_aligned.values():
            if self.planner_exchange_keys and al:
                n_sk += 1
            else:
                n_ex += 1
        return n_ex, n_sk

    # -------------------------------------------------------------- #
    # round builders (jitted shard_map variants, cached per schedule)
    # -------------------------------------------------------------- #
    def _variant(self, tag, build) -> _Variant:
        rec = self._variants.get(tag)
        if rec is None:
            rec = build()
            self._variants[tag] = rec
        return rec

    def _evict_stale_factors(self) -> None:
        """Drop round variants traced at superseded padding factors
        (their keys end in the int factor).  A regrow retraces the live
        schedules at the new factor; keeping every historical factor's
        compiled executables alive would be a slow memory leak on
        long-running update loops."""
        self._variants = {
            k: v
            for k, v in self._variants.items()
            if not isinstance(k[-1], int) or k[-1] == self._factor
        }

    @staticmethod
    def _plan_signature(rule, plan) -> tuple:
        """Everything about a plan that shapes its trace: atom order,
        source partitions, and the exchange key.  Re-plans that land on
        the same physical plan (the common case after a cardinality
        bucket shift) therefore reuse the compiled round."""
        steps = [plan.first] + [j.scan for j in plan.joins]
        return (
            rule.head,
            tuple((s.atom, s.source) for s in steps),
            plan.joins[0].partition_key if plan.joins else None,
        )

    def _layout(self) -> tuple:
        """The predicate tuple (the buffer layout) and the key words: a
        variant's key holds both, so the variant cache survives
        re-materialisation over the same schema (warm fixpoints time
        rounds, not re-tracing), and wider ids select other variants."""
        return (self._preds, self._key_words)

    def _pair_key(self, pairs) -> tuple:
        return (self._layout(),) + tuple(
            self._plan_signature(r, pl) for r, _pv, pl in pairs
        )

    def _spec3(self):
        return [P(self.axis, None, None), P(self.axis), P(self.axis)]

    def _spec2(self):
        return [P(self.axis, None, None), P(self.axis)]

    def _shmap(self, kind, body, in_specs, out_specs, donate_argnums=()):
        """The jitted ``shard_map`` of ``body``; its program is named
        ``jit_body_<kind>`` (``round``, ``acc_round``, ``delete``,
        ``merge``) in traces and compiler dumps."""
        body.__name__ = body.__qualname__ = f"body_{kind}"
        return jax.jit(jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=tuple(in_specs),
            out_specs=tuple(out_specs),
            # pallas_call outputs have no varying-axes metadata; disable
            # the vma check so the kernel dedup path can run under
            # shard_map (the specs above still pin the layouts)
            check_vma=False,
        ), donate_argnums=tuple(donate_argnums))

    def _state_donation(self):
        """Argnums of the per-predicate state buffers, for variants that
        consume-and-replace the state exactly once per call (delete /
        merge — NOT the fixpoint rounds, which retry the *same* inputs
        on exchange overflow and so must never donate).  Donation lets
        XLA reuse the old buffers for the outputs, so steady-state
        maintenance allocates nothing.  Every backend honours it (the
        CPU one included), so a donated input read afterwards fails in
        the CPU tests exactly as it does on the chip."""
        return tuple(range(3 * len(self._preds)))

    def _merge_block(self, trows, tcnt, rows, valid, restrict=None, words=1):
        """Dedup candidate rows against a target buffer (and optionally
        restrict them to a membership set), then append — the shared
        tail of every round/seed; keys of ``words`` int32 words.
        Returns (rows', cnt', fresh, overflow)."""
        cap = trows.shape[0]
        with jax.named_scope("keys"):
            keys = pack_pairs(rows, words)
            tvalid = jnp.arange(cap) < tcnt
            tsorted = sort_keys(pack_pairs(trows, words), tvalid)
            rsorted = None
            if restrict is not None:
                rrows, rcnt = restrict
                rvalid = jnp.arange(rrows.shape[0]) < rcnt
                rsorted = sort_keys(pack_pairs(rrows, words), rvalid)
        with jax.named_scope("dedup"):
            fresh = dedup_against(
                keys, valid, tsorted, member_fn=self._member_fn,
                restrict_sorted=rsorted,
            )
        n_fresh = jnp.sum(fresh.astype(jnp.int32))
        overflow = jnp.maximum(tcnt + n_fresh - cap, 0)
        dest = tcnt + jnp.cumsum(fresh.astype(jnp.int32)) - 1
        ok = fresh & (dest < cap)
        # park non-fresh writes *out of bounds* so the scatter drops
        # them: parking at cap-1 would collide with a fresh write there
        # whenever an append exactly fills the buffer (duplicate-index
        # scatter order is undefined, and the stale value could win)
        dest = jnp.where(ok, dest, cap)
        nrows = trows.at[dest].set(
            jnp.where(ok[:, None], rows, EMPTY), mode="drop"
        )
        ncnt = jnp.minimum(tcnt + n_fresh, cap)
        return nrows, ncnt, n_fresh, overflow

    def _build_round(self, pairs, *, acc_mode, union_acc, use_restrict, factor):
        """One fixpoint round: evaluate every scheduled (rule, pivot)
        plan locally, exchange derivations to their owner shards, dedup,
        append into the delta partitions.

        ``acc_mode`` evaluates against a read-only *base* (the current
        materialisation) while accumulating into separate per-predicate
        buffers — the overdelete/rederive phases of ``apply`` (with
        ``union_acc`` the accumulator is unioned into old/all reads, and
        ``use_restrict`` keeps only candidates inside a membership set).
        """
        preds, axis, words = self._preds, self.axis, self._key_words

        def body(*flat):
            k = 0
            base: dict = {}
            accs: dict = {}
            restrict: dict = {}
            if acc_mode:
                for p in preds:
                    base[p] = (flat[k][0], flat[k + 1][0])
                    k += 2
                for p in preds:
                    accs[p] = (flat[k][0], flat[k + 1][0], flat[k + 2][0])
                    k += 3
                if use_restrict:
                    for p in preds:
                        restrict[p] = (flat[k][0], flat[k + 1][0])
                        k += 2
            else:
                for p in preds:
                    base[p] = (flat[k][0], flat[k + 1][0], flat[k + 2][0])
                    k += 3

            def part(pred, src):
                if not acc_mode:
                    rows, cnt, lo = base[pred]
                    idx = jnp.arange(rows.shape[0])
                    if src == SRC_DELTA:
                        return rows, (idx >= lo) & (idx < cnt)
                    if src == SRC_OLD:
                        return rows, idx < lo
                    return rows, idx < cnt
                arows, acnt, alo = accs[pred]
                aidx = jnp.arange(arows.shape[0])
                if src == SRC_DELTA:
                    return arows, (aidx >= alo) & (aidx < acnt)
                brows, bcnt = base[pred]
                bvalid = jnp.arange(brows.shape[0]) < bcnt
                if union_acc:
                    return (
                        jnp.concatenate([brows, arows]),
                        jnp.concatenate([bvalid, aidx < acnt]),
                    )
                return brows, bvalid

            derived: dict[str, list] = {}

            def emit(pred, rows, valid, aligned):
                derived.setdefault(pred, []).append((rows, valid, aligned))

            dropped = jnp.zeros((), jnp.int32)
            joined = jnp.zeros((), jnp.int32)
            # phase scopes name the ops in traces: join (one r<rule id>
            # sub-scope a pair), exchange, merge and, inside it, dedup
            with jax.named_scope("join"):
                for rule, _pivot, plan in pairs:
                    with jax.named_scope(f"r{self._rule_ids.get(rule, -1)}"):
                        d, j = self._trace_pair(rule, plan, part, emit, factor)
                    dropped = dropped + d
                    joined = joined + j

            new_flat = []
            new_counts = []
            total_new = jnp.zeros((), jnp.int32)
            overflow = jnp.zeros((), jnp.int32)
            for pred in preds:
                if acc_mode:
                    trows, tcnt, _tlo = accs[pred]
                else:
                    trows, tcnt, _tlo = base[pred]
                blocks = derived.get(pred, [])
                if not blocks:
                    # no derivations: the delta still gets consumed
                    new_flat.extend([trows[None], tcnt[None], tcnt[None]])
                    new_counts.append(tcnt[None])
                    continue
                rows = jnp.concatenate([b[0] for b in blocks])
                valid = jnp.concatenate([b[1] for b in blocks])
                aligned = all(b[2] for b in blocks)
                rows = jnp.where(valid[:, None], rows, EMPTY)
                # route each derivation to the shard owning its head key
                if self.n_shards > 1 and not (
                    self.planner_exchange_keys and aligned
                ):
                    with jax.named_scope("exchange"):
                        rows, valid, d = self._exchange(rows, valid, factor)
                    dropped = dropped + d
                with jax.named_scope("merge"):
                    nrows, ncnt, n_fresh, of = self._merge_block(
                        trows, tcnt, rows, valid,
                        restrict=restrict.get(pred) if use_restrict else None,
                        words=words,
                    )
                total_new = total_new + n_fresh
                overflow = overflow + of
                new_flat.extend([nrows[None], ncnt[None], tcnt[None]])
                new_counts.append(ncnt[None])

            scalars = (total_new, dropped, overflow, joined)
            return tuple(new_flat) + (_packed_block(
                [jax.lax.psum(x, axis)[None] for x in scalars] + new_counts
            ),)

        in_specs: list = []
        if acc_mode:
            for _ in preds:
                in_specs.extend(self._spec2())
            for _ in preds:
                in_specs.extend(self._spec3())
            if use_restrict:
                for _ in preds:
                    in_specs.extend(self._spec2())
        else:
            for _ in preds:
                in_specs.extend(self._spec3())
        out_specs: list = []
        for _ in preds:
            out_specs.extend(self._spec3())
        out_specs.append(P(self.axis))
        n_ex, n_sk = self._static_exchange_counts(pairs)
        kind = "acc_round" if acc_mode else "round"
        return _Variant(
            self._shmap(kind, body, in_specs, out_specs), n_ex, n_sk
        )

    def _build_delete(self):
        """Per-shard deletion: drop routed rows from every predicate's
        buffer and compact survivors to the front (delta emptied)."""
        preds, words = self._preds, self._key_words
        member_fn = self._member_fn

        def body(*flat):
            k = 0
            st: dict = {}
            de: dict = {}
            for p in preds:
                st[p] = (flat[k][0], flat[k + 1][0], flat[k + 2][0])
                k += 3
            for p in preds:
                de[p] = (flat[k][0], flat[k + 1][0])
                k += 2
            out = []
            for p in preds:
                rows, cnt, _lo = st[p]
                drows, dcnt = de[p]
                cap = rows.shape[0]
                idx = jnp.arange(cap)
                slot = idx < cnt
                with jax.named_scope("keys"):
                    keys = tuple(
                        jnp.where(slot, w, BIG) for w in pack_pairs(rows, words)
                    )
                    dvalid = jnp.arange(drows.shape[0]) < dcnt
                    dsorted = sort_keys(pack_pairs(drows, words), dvalid)
                keep = slot & ~member_fn(keys, dsorted)
                n_keep = jnp.sum(keep.astype(jnp.int32))
                perm = jnp.argsort(
                    jnp.where(keep, idx, cap + idx), stable=False
                )
                nrows = jnp.where((idx < n_keep)[:, None], rows[perm], EMPTY)
                out.extend([nrows[None], n_keep[None], n_keep[None]])
            return tuple(out) + (_packed_block(out[1::3]),)

        in_specs: list = []
        for _ in preds:
            in_specs.extend(self._spec3())
        for _ in preds:
            in_specs.extend(self._spec2())
        out_specs: list = []
        for _ in preds:
            out_specs.extend(self._spec3())
        out_specs.append(P(self.axis))
        return _Variant(
            self._shmap("delete", body, in_specs, out_specs), 0, 0
        )

    def _build_merge(self):
        """Per-shard seed/fold-in: dedup routed host rows against each
        predicate's buffer and append them as the new delta."""
        preds, axis, words = self._preds, self.axis, self._key_words

        def body(*flat):
            k = 0
            st: dict = {}
            ad: dict = {}
            for p in preds:
                st[p] = (flat[k][0], flat[k + 1][0], flat[k + 2][0])
                k += 3
            for p in preds:
                ad[p] = (flat[k][0], flat[k + 1][0])
                k += 2
            out = []
            total_new = jnp.zeros((), jnp.int32)
            overflow = jnp.zeros((), jnp.int32)
            for p in preds:
                rows, cnt, _lo = st[p]
                arows, acnt = ad[p]
                avalid = jnp.arange(arows.shape[0]) < acnt
                with jax.named_scope("merge"):
                    nrows, ncnt, n_fresh, of = self._merge_block(
                        rows, cnt, arows, avalid, words=words
                    )
                total_new = total_new + n_fresh
                overflow = overflow + of
                out.extend([nrows[None], ncnt[None], cnt[None]])
            return tuple(out) + (_packed_block(
                [jax.lax.psum(x, axis)[None] for x in (total_new, overflow)]
                + out[1::3]
            ),)

        in_specs: list = []
        for _ in preds:
            in_specs.extend(self._spec3())
        for _ in preds:
            in_specs.extend(self._spec2())
        out_specs: list = []
        for _ in preds:
            out_specs.extend(self._spec3())
        out_specs.append(P(self.axis))
        return _Variant(
            self._shmap(
                "merge", body, in_specs, out_specs,
                donate_argnums=self._state_donation(),
            ),
            0, 0,
        )

    # -------------------------------------------------------------- #
    # round execution with exchange-regrow retries
    # -------------------------------------------------------------- #
    def _run_round(self, build_variant, flat):
        """Run one jitted round; on exchange/join overflow, double the
        padding factor and retry the *same* inputs (rounds are pure, so
        nothing was committed).  Returns the outputs, the packed block
        last and read to the host, with the round's new facts and joined
        rows."""
        regrew = False
        n = len(ROUND_SCALARS)
        for _ in range(self.max_regrows + 1):
            # the enqueue: variant lookup (trace and compile on a miss)
            # and the call; then the block on the round's one read
            with span("dist.launch"):
                rec = build_variant()
                out = rec.fn(*flat)
            with span("dist.wait"):
                block = self._fetch(out[-1])
            total_new, dropped, overflow, joined = (
                int(x) for x in block[0, :n]
            )
            if overflow > 0:
                raise RuntimeError(
                    f"relation buffer overflow: {overflow} rows past "
                    f"capacity {self.capacity} — increase capacity"
                )
            if dropped == 0:
                if regrew:
                    self._evict_stale_factors()
                self.stats.exchanges += rec.n_exchanges
                self.stats.exchanges_skipped += rec.n_exchanges_skipped
                self.stats.rows_joined += joined
                return (*out[:-1], block[:, n:]), total_new, joined
            self._factor *= 2
            regrew = True
            self.stats.exchange_regrows += 1
            instant("dist.exchange_regrow", factor=self._factor)
        raise RuntimeError(
            "exchange overflow persists after "
            f"{self.max_regrows} regrows — increase capacity/join_capacity"
        )

    def _mat_round(self, pairs):
        """One materialise/insert round over the live partitions."""

        def build():
            return self._variant(
                ("mat", self._pair_key(pairs), self._factor),
                lambda: self._build_round(
                    pairs, acc_mode=False, union_acc=False,
                    use_restrict=False, factor=self._factor,
                ),
            )

        out, total_new, joined = self._run_round(build, self._flat_state())
        self._take_state(out)
        return total_new, joined

    def _take_state(self, out, *, consumed: bool = False) -> None:
        """Adopt a program's per-predicate outputs as the state, and the
        new counts that ``out`` ends with (its packed block, already on
        the host, less the scalars) as the mirror's: each watermark
        becomes the previous count — the program appended its new rows
        as the delta — or, ``consumed``, the new count (no delta left)."""
        counts = out[-1]
        for i, p in enumerate(self._preds):
            self._state[p] = list(out[3 * i : 3 * i + 3])
            cnt = counts[:, i].copy()
            self._set_mirror(p, cnt, cnt if consumed else self._mirror[p][0])

    def _acc_round(self, acc, pairs, *, union_acc, restrict):
        """One accumulator round (overdelete / rederive phases)."""
        pkey = self._pair_key(pairs)
        flat = []
        for p in self._preds:
            flat.extend(self._state[p][:2])
        for p in self._preds:
            flat.extend(acc[p])
        if restrict is not None:
            for p in self._preds:
                flat.extend(restrict[p])

        def build():
            return self._variant(
                ("acc", pkey, union_acc, restrict is not None, self._factor),
                lambda: self._build_round(
                    pairs, acc_mode=True, union_acc=union_acc,
                    use_restrict=restrict is not None, factor=self._factor,
                ),
            )

        out, total_new, _joined = self._run_round(build, flat)
        for i, p in enumerate(self._preds):
            acc[p] = list(out[3 * i : 3 * i + 3])
        return total_new

    # -------------------------------------------------------------- #
    # host-side scheduling (the semi-naive skip logic)
    # -------------------------------------------------------------- #
    def _schedule(self, stratum, entry: bool, stable: bool = False):
        """(rule, pivot) pairs to evaluate this round + pairs skipped
        without a probe (no delta on the pivot, or an empty body
        predicate) — the host-side mirror of CMatEngine._round.

        ``stable=True`` (the apply sweeps) schedules every pair so each
        stratum traces one round variant regardless of which predicates
        the batch happened to touch; materialisation keeps the
        fine-grained skip (its delta patterns are stable per stratum, so
        the skip saves device work without trace churn)."""
        pairs = []
        skipped = 0
        if stable:
            pairs = [
                (rule, i)
                for rule in stratum
                for i in range(len(rule.body))
            ]
            return self._resolve(pairs, frozen=True), 0
        if entry:
            # first round of a stratum: nothing of it ever ran, evaluate
            # each rule once over everything derived so far (pivot=None)
            for rule in stratum:
                if not rule.body:
                    continue
                if any(
                    self._counts.get(a.predicate, 0) == 0 for a in rule.body
                ):
                    skipped += 1
                    continue
                pairs.append((rule, None))
            return self._resolve(pairs), skipped
        delta_preds = {p for p in self._preds if self._delta_count(p) > 0}
        for rule in stratum:
            for i, atom in enumerate(rule.body):
                if atom.predicate not in delta_preds:
                    skipped += 1
                    continue
                if any(
                    self._counts.get(a.predicate, 0) == 0 for a in rule.body
                ):
                    skipped += 1
                    continue
                pairs.append((rule, i))
        return self._resolve(pairs), skipped

    def _stratum_fixpoint(
        self, si, stratum, max_rounds, *, naive_entry, sweep_lo=None,
        stable=False,
    ) -> tuple[int, bool]:
        """Run one stratum to its fixpoint; returns ``(rounds used,
        converged)`` — ``converged=False`` means the round budget ran out
        with work still pending (the caller must raise, never silently
        return an incomplete materialisation).

        ``sweep_lo`` (incremental insertion sweeps) re-marks everything
        appended since the sweep started as this stratum's incoming
        delta — each stratum sees the net additions of the strata below.
        """
        heads, body_preds = stratum_predicates(stratum)
        if sweep_lo is not None:
            for p in self._preds:
                self._state[p][2] = self._to_shards(sweep_lo[p])
                self._set_mirror(p, self._mirror[p][0], sweep_lo[p])
        entry = naive_entry
        rounds = 0
        r0 = len(self.stats.per_round)
        with span("dist.stratum", stratum=si, rules=len(stratum)):
            while rounds < max_rounds:
                with span("dist.schedule"):
                    if (
                        not entry
                        and self.seminaive
                        and not self._any_delta(body_preds)
                    ):
                        break
                    pairs, skipped = self._schedule(
                        stratum, entry, stable=stable
                    )
                self.stats.rule_applications_skipped += skipped
                if not pairs:
                    break
                round_no = len(self.stats.per_round) + 1
                counts_before = None
                if self._pjournal is not None:
                    counts_before = {p: self._mirror[p][0] for p in self._preds}
                with span(
                    "dist.round",
                    round=round_no,
                    stratum=si,
                    rule_applications=len(pairs),
                ) as sp:
                    if sp.recording:
                        sp.set(rule_ids=sorted({
                            self._rule_ids.get(rule, -1)
                            for rule, _p, _pl in pairs
                        }))
                    total_new, joined = self._mat_round(pairs)
                    sp.set(new_facts=total_new, rows_joined=joined)
                if counts_before is not None:
                    for rule, pivot, _plan in pairs:
                        self._record_dist(
                            "schedule", rule.head.predicate,
                            stratum=si, round_no=round_no,
                            rule_id=self._rule_ids.get(rule, -1),
                            pivot=-1 if pivot is None else pivot,
                        )
                    for p in self._preds:
                        grow = self._mirror[p][0] - counts_before[p]
                        for s in np.nonzero(grow)[0]:
                            self._record_dist(
                                "apply", p, stratum=si, round_no=round_no,
                                n_new=int(grow[s]), shard=int(s),
                            )
                rounds += 1
                self.stats.n_rule_applications += len(pairs)
                self.stats.per_round.append(
                    {
                        "round": len(self.stats.per_round) + 1,
                        "stratum": si,
                        "new_facts": total_new,
                        "rows_joined": joined,
                        "rule_applications": len(pairs),
                        "rule_applications_skipped": skipped,
                    }
                )
                if self.seminaive:
                    entry = False
                if total_new == 0:
                    break
        self.stats.per_stratum.append(
            {
                "stratum": si,
                "rounds": rounds,
                "rules": len(stratum),
                "heads": sorted(heads),
                "rule_applications": sum(
                    r["rule_applications"]
                    for r in self.stats.per_round[r0:]
                ),
            }
        )
        # budget exhausted with work pending?  (the loop breaks on empty
        # schedules / empty rounds, so exiting via the while-condition
        # means the last round still derived facts, or it never ran)
        pending = False
        if rounds >= max_rounds:
            if entry:
                pairs, _ = self._schedule(stratum, True, stable=stable)
                pending = bool(pairs)
            else:
                pending = self._any_delta(body_preds)
        return rounds, not pending

    # -------------------------------------------------------------- #
    # materialisation
    # -------------------------------------------------------------- #
    def _prepare(self, dataset: dict[str, np.ndarray]) -> None:
        preds = tuple(sorted(set(dataset) | self.program.predicates()))
        arities: dict[str, int] = {}
        for p in preds:
            if p in dataset:
                r = np.asarray(dataset[p])
                arities[p] = 1 if r.ndim == 1 else r.shape[1]
        for rule in self.program:
            for atom in (rule.head, *rule.body):
                arities.setdefault(atom.predicate, atom.arity)
        for p, a in arities.items():
            if a > 2:
                raise NotImplementedError(
                    f"distributed engine supports arity <= 2 ({p!r} has {a})"
                )
        full = {}
        for p in preds:
            rows = np.asarray(
                dataset.get(p, np.zeros((0, arities[p]))), dtype=np.int64
            )
            if rows.ndim == 1:
                rows = rows.reshape(-1, 1)
            self._check_const_range(p, rows)
            full[p] = unique_rows(rows) if rows.shape[0] else rows
        self._preds = preds
        self._arities = arities
        self._key_words = key_words(self._rule_top)
        self._see_ids(full)
        self.explicit = {
            p: rows for p, rows in full.items() if rows.shape[0]
        }
        self._factor = 1
        self._dirty = False
        routed = self._route(
            {p: rows.astype(np.int32) for p, rows in full.items()}
        )
        self._state = {}
        self._mirror = {}
        self._counts = {}
        for p in preds:
            rows, cnt = self._sharded_pair(routed, p)
            self._state[p] = [rows, cnt, jnp.zeros_like(cnt)]
            host_cnt = routed[p][1]
            self._set_mirror(p, host_cnt, np.zeros_like(host_cnt))

    def materialise(self, dataset: dict[str, np.ndarray], max_rounds: int = 64):
        """Run rounds to fixpoint; returns per-predicate host arrays."""
        with span("dist.materialise", n_shards=self.n_shards) as sp:
            with span("dist.prepare"):
                self._prepare(dataset)
            self.stats = DistributedStats()
            from ..obs.provenance import get_journal

            journal = get_journal()
            self._pjournal = journal if journal.enabled else None
            if self._pjournal is not None:
                self._pjournal.attach_program(self.program)
            with span("dist.schedule"):
                strata = (
                    stratify(self.program)
                    if self.seminaive
                    else [list(self.program)]
                )
            self.stats.n_strata = len(strata)
            self.stats.key_words = self._key_words
            sp.set(n_strata=len(strata), key_words=self._key_words)
            rounds = 0
            for si, stratum in enumerate(strata):
                used, converged = self._stratum_fixpoint(
                    si, stratum, max_rounds - rounds, naive_entry=True
                )
                rounds += used
                if not converged:
                    raise RuntimeError(
                        f"materialisation did not reach a fixpoint within "
                        f"max_rounds={max_rounds} (stratum {si} still has "
                        f"pending deltas) — increase max_rounds"
                    )
            with span("dist.pull"):
                pulled = self._pull()
                result = {
                    p: pulled.get(p, np.zeros((0, self._arities[p]), np.int64))
                    for p in self._preds
                }
        self.rounds = rounds
        self.stats.rounds = rounds
        self.stats.plan_cache = self._plan_cache.counters()
        # published after the pull, so that its reads are counted
        publish_distributed(self.stats)
        if self._pjournal is not None:
            self._pjournal.publish()
        return result

    # -------------------------------------------------------------- #
    # incremental maintenance: deltas through the exchange
    # -------------------------------------------------------------- #
    def _new_acc(self, seeds: dict[str, np.ndarray] | None = None) -> dict:
        acc = {}
        routed = self._route(
            {
                p: np.asarray(r, np.int64).astype(np.int32)
                for p, r in (seeds or {}).items()
                if np.asarray(r).shape[0]
            }
        )
        for p in self._preds:
            rows, cnt = self._sharded_pair(routed, p)
            acc[p] = [rows, cnt, jnp.zeros_like(cnt)]
        return acc

    def _pull_acc(self, acc: dict) -> dict[str, np.ndarray]:
        out = {}
        with span("dist.pull"):
            for p in self._preds:
                buf = self._fetch(acc[p][0])
                cnt = self._fetch(acc[p][1])
                if cnt.sum() == 0:
                    continue
                rows = np.concatenate(
                    [buf[s, : cnt[s]] for s in range(self.n_shards)]
                )
                out[p] = unique_rows(rows.astype(np.int64))
        return out

    def _route_pairs(self, rows_by_pred: dict) -> dict:
        """(rows, cnt) jnp buffers per predicate (zero-filled when the
        predicate has no rows in the batch)."""
        routed = self._route(
            {
                p: np.asarray(r, np.int64).astype(np.int32)
                for p, r in rows_by_pred.items()
                if np.asarray(r).shape[0]
            }
        )
        return {p: list(self._sharded_pair(routed, p)) for p in self._preds}

    def _schedule_acc(self, rules, *, one_step: bool):
        """(rule, pivot) pairs for an accumulator round: the pivot reads
        the accumulator's delta (or ``None`` for the one-step
        rederivability check, which re-evaluates whole bodies).

        Deliberately *stable* — every pair is scheduled regardless of
        which predicates currently hold deltas, so each apply phase
        traces exactly one round variant and every later batch reuses
        it.  An empty delta partition joins to nothing on device, which
        costs far less than re-tracing per delta combination (update
        batches hit arbitrary predicate subsets)."""
        if one_step:
            pairs = [(rule, None) for rule in rules if rule.body]
        else:
            pairs = [
                (rule, i)
                for rule in rules
                for i in range(len(rule.body))
            ]
        return self._resolve(pairs, frozen=True)

    def apply(
        self,
        additions: dict[str, np.ndarray] | None = None,
        deletions: dict[str, np.ndarray] | None = None,
    ) -> DistributedStats:
        """Incrementally maintain the sharded materialisation for
        ``E' = (E \\ deletions) ∪ additions``.

        Deletion batches run the DRed phases of
        :mod:`repro.incremental.dred` set-at-a-time over the shards —
        overdelete / delete / rederive deltas all ship through the same
        ``all_to_all`` exchange as materialisation rounds — and addition
        batches run the stratified semi-naive insertion sweep.  Batches
        are clamped against the explicit set exactly like the host
        :class:`~repro.incremental.IncrementalStore` (idempotence), so
        the two stay differentially comparable via
        :meth:`check_integrity`.
        """
        import time

        from ..incremental.store import effective_updates, normalise_batch

        if self._state is None:
            raise RuntimeError("materialise() must run before apply()")
        if self._dirty:
            raise RuntimeError(
                "a previous apply() failed mid-sweep; the sharded state "
                "is inconsistent — materialise() again before applying"
            )
        t0 = time.perf_counter()
        st = DistributedStats()
        self.stats = st
        from ..obs.provenance import get_journal

        journal = get_journal()
        self._pjournal = journal if journal.enabled else None
        if self._pjournal is not None:
            self._pjournal.begin_epoch(self.epoch + 1)
            self._pjournal.attach_program(self.program)
        adds = normalise_batch(additions)
        dels = normalise_batch(deletions)
        unknown = (set(adds) | set(dels)) - set(self._preds)
        if unknown:
            raise NotImplementedError(
                f"apply() over predicates absent at materialise time: "
                f"{sorted(unknown)}"
            )
        # validate the whole batch BEFORE any mutation: a rejection after
        # effective_updates has touched self.explicit would permanently
        # desynchronise the explicit set from the shards
        for batch in (adds, dels):
            for pred, rows in batch.items():
                self._check_const_range(pred, rows)
        self._see_ids(adds, dels)
        st.key_words = self._key_words
        # E := E \ D, swept before the additions clamp (same phase order
        # as IncrementalStore.apply)
        self._dirty = True
        with span(
            "dist.apply",
            n_additions=sum(int(r.shape[0]) for r in adds.values()),
            n_deletions=sum(int(r.shape[0]) for r in dels.values()),
        ):
            _, eff_dels = effective_updates(self.explicit, {}, dels)
            st.n_del_explicit += sum(
                int(r.shape[0]) for r in eff_dels.values()
            )
            if eff_dels:
                self._deletion_sweep(eff_dels, st)
            eff_adds, _ = effective_updates(self.explicit, adds, {})
            st.n_add_explicit += sum(
                int(r.shape[0]) for r in eff_adds.values()
            )
            if eff_adds:
                self._insertion_sweep(eff_adds, st)
        self._dirty = False
        self.epoch += 1
        st.epoch = self.epoch
        st.plan_cache = self._plan_cache.counters()
        st.time_total = time.perf_counter() - t0
        publish_distributed(st)
        if self._pjournal is not None:
            self._pjournal.publish()
        return st

    def _deletion_sweep(self, dels: dict[str, np.ndarray], st) -> None:
        """DRed over the shards: overdelete (delta exchange over the
        pre-deletion view), physical delete, rederive (explicit
        restores + one-step check + forward propagation)."""
        from ..incremental.dred import explicit_restores
        from ..incremental.index import setdiff_rows

        rules = [r for r in self.program if r.body]
        # --- overdelete: propagate the deleted delta ------------------- #
        with span("dist.overdelete") as sp:
            over_acc = self._new_acc(dels)
            while True:
                pairs = self._schedule_acc(rules, one_step=False)
                if not pairs:
                    break
                st.n_rule_applications += len(pairs)
                total_new = self._acc_round(
                    over_acc, pairs, union_acc=False,
                    restrict={p: self._state[p][:2] for p in self._preds},
                )
                if total_new == 0:
                    break
            over = self._pull_acc(over_acc)
            n_over = sum(int(r.shape[0]) for r in over.values())
            st.n_overdeleted += n_over
            sp.set(n_overdeleted=n_over)
            for pred, rows in over.items():
                if rows.shape[0]:
                    self._record_dist(
                        "overdelete", pred, n_new=int(rows.shape[0])
                    )

        # --- delete: drop overdeleted rows from every shard ------------ #
        with span("dist.delete"):
            routed = self._route_pairs(over)
            flat = self._flat_state()
            for p in self._preds:
                flat.extend(routed[p])
            with span("dist.launch"):
                rec = self._variant(
                    ("delete", self._layout()), self._build_delete
                )
                out = rec.fn(*flat)
            with span("dist.sync"):
                block = self._fetch(out[-1])
            self._take_state((*out[:-1], block), consumed=True)

        # --- rederive: explicit restores, one-step check, forward ------ #
        with span("dist.rederive") as sp:
            restored0 = explicit_restores(over, self.explicit)
            missing = {
                p: setdiff_rows(rows, restored0[p]) if p in restored0 else rows
                for p, rows in over.items()
            }
            missing = {p: r for p, r in missing.items() if r.shape[0]}
            red_acc = self._new_acc(restored0)
            if missing and rules:
                restrict = self._route_pairs(missing)
                pairs = self._schedule_acc(rules, one_step=True)
                if pairs:
                    st.n_rule_applications += len(pairs)
                    self._acc_round(
                        red_acc, pairs, union_acc=True, restrict=restrict
                    )
                while True:
                    pairs = self._schedule_acc(rules, one_step=False)
                    if not pairs:
                        break
                    st.n_rule_applications += len(pairs)
                    total_new = self._acc_round(
                        red_acc, pairs, union_acc=True, restrict=restrict
                    )
                    if total_new == 0:
                        break
            restored = self._pull_acc(red_acc)
            n_restored = sum(int(r.shape[0]) for r in restored.values())
            st.n_rederived += n_restored
            sp.set(n_rederived=n_restored)
            for pred, rows in restored.items():
                if rows.shape[0]:
                    self._record_dist(
                        "rederive", pred, n_new=int(rows.shape[0])
                    )

            # --- fold restorations back into the base partitions ------- #
            if n_restored:
                self._merge_host_rows(restored, st, count_inserted=False)
            st.n_deleted += (
                sum(int(r.shape[0]) for r in over.values()) - n_restored
            )

    def _merge_host_rows(self, rows_by_pred, st, *, count_inserted) -> int:
        """Route host rows to their owner shards and dedup-append them as
        the new delta; returns the number of genuinely fresh facts."""
        routed = self._route_pairs(rows_by_pred)
        flat = self._flat_state()
        for p in self._preds:
            flat.extend(routed[p])
        with span("dist.launch"):
            rec = self._variant(("merge", self._layout()), self._build_merge)
            out = rec.fn(*flat)
        with span("dist.wait"):
            block = self._fetch(out[-1])
        n = len(MERGE_SCALARS)
        fresh, overflow = (int(x) for x in block[0, :n])
        if overflow > 0:
            raise RuntimeError(
                f"relation buffer overflow: {overflow} rows past capacity "
                f"{self.capacity} — increase capacity"
            )
        self._take_state((*out[:-1], block[:, n:]))
        if count_inserted:
            st.n_inserted += fresh
        return fresh

    def _insertion_sweep(self, adds: dict[str, np.ndarray], st) -> None:
        """Stratified semi-naive insertion: the added facts are the
        incoming delta; every stratum re-marks the sweep's net additions
        as its delta (the ``sweep_lo`` watermark), so derived facts of
        earlier strata propagate without host-side seed bookkeeping."""
        with span("dist.insert") as sp:
            sweep_lo = {p: self._mirror[p][0].copy() for p in self._preds}
            self._merge_host_rows(adds, st, count_inserted=True)
            strata = (
                stratify(self.program)
                if self.seminaive
                else [list(self.program)]
            )
            r0 = len(self.stats.per_round)
            for si, stratum in enumerate(strata):
                _, converged = self._stratum_fixpoint(
                    si, stratum, 512, naive_entry=False, sweep_lo=sweep_lo,
                    stable=True,
                )
                if not converged:
                    raise RuntimeError(
                        f"insertion sweep did not reach a fixpoint in "
                        f"stratum {si} within 512 rounds"
                    )
            st.n_inserted += sum(
                r["new_facts"] for r in self.stats.per_round[r0:]
            )
            st.rounds += len(self.stats.per_round) - r0
            sp.set(n_inserted=st.n_inserted)

    # -------------------------------------------------------------- #
    # read side / differential checking
    # -------------------------------------------------------------- #
    def to_dict(self) -> dict[str, np.ndarray]:
        """Flat per-predicate materialisation (sorted unique int64 rows,
        empty predicates omitted — the IncrementalStore contract)."""
        with span("dist.pull"):
            return self._pull()

    def _pull(self) -> dict[str, np.ndarray]:
        """The state's facts on the host (sorted unique int64 rows) of
        every predicate that has any: counts from the mirror, and every
        such buffer read in one wait."""
        live = [p for p in self._preds if self._counts[p]]
        if not live:
            return {}
        bufs = self._fetch([self._state[p][0] for p in live])
        out = {}
        for p, buf in zip(live, bufs):
            cnt = self._mirror[p][0]
            flat_rows = np.concatenate(
                [buf[s, : cnt[s]] for s in range(self.n_shards)]
            )
            out[p] = unique_rows(flat_rows.astype(np.int64))
        return out

    def check_integrity(self, host) -> None:
        """Differentially compare the sharded materialisation against a
        host engine maintained with the same batches (an
        :class:`~repro.incremental.IncrementalStore`, or any object with
        ``to_dict()``, or a plain ``{pred: rows}`` dict)."""
        if self._pjournal is not None:
            self._pjournal.merge_shard_records()
        want = host.to_dict() if hasattr(host, "to_dict") else dict(host)
        got = self.to_dict()
        want = {p: r for p, r in want.items() if np.asarray(r).shape[0]}
        errs = []
        for p in sorted(set(want) | set(got)):
            a = {tuple(map(int, r)) for r in np.asarray(want.get(p, [])).reshape(-1, self._arities.get(p, 1))} if p in want else set()
            b = {tuple(map(int, r)) for r in got[p]} if p in got else set()
            if a != b:
                errs.append(
                    f"{p!r}: host-only={len(a - b)} shard-only={len(b - a)}"
                )
        if errs:
            raise AssertionError(
                "distributed materialisation diverged from host: "
                + "; ".join(errs)
            )

    # -------------------------------------------------------------- #
    # lowering hook (dryrun/roofline)
    # -------------------------------------------------------------- #
    def abstract_round(self, preds, arities):
        """One jitted naive round + its abstract input shapes, for HLO
        lowering without any data (``launch.dryrun_datalog``)."""
        self._preds = tuple(preds)
        self._arities = dict(arities)
        self._counts = {p: self.capacity for p in preds}
        self._variants = {}
        pairs = self._resolve(
            [(r, None) for r in self.program if r.body]
        )
        rec = self._build_round(
            pairs, acc_mode=False, union_acc=False,
            use_restrict=False, factor=1,
        )
        shapes = []
        for p in self._preds:
            shapes.append(
                jax.ShapeDtypeStruct(
                    (self.n_shards, self.capacity, self._arities[p]), np.int32
                )
            )
            shapes.append(jax.ShapeDtypeStruct((self.n_shards,), np.int32))
            shapes.append(jax.ShapeDtypeStruct((self.n_shards,), np.int32))
        return rec.fn, shapes
