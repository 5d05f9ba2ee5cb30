"""Resilience layer: one retry policy, typed give-up errors, deterministic
fault injection.

The paper's asynchronous protocol leans entirely on static capacities plus
drop-count-and-retry: a routed tile that overflows is replayed at doubled
slack, a full count store is rehashed into doubled capacity, a compact
hop-2 tile that misfits falls back to the padded tile (the second capacity
of the KMC 3-style scheme). Before this module those three disciplines
lived in three ad-hoc loops; now they are one bounded, typed engine:

- `RetryPolicy` -- the knobs (per-cause caps, growth factors, a total
  round budget), configurable on `fabsp.DAKCConfig.retry`. The defaults
  reproduce the historical behaviour exactly (slack gives up past 8, the
  store past 2**28 slots).
- `RetryController` -- per-call driver state. Call sites run the attempt,
  feed the drop counters to `observe()`, and either loop (the controller
  doubled the right knob and recorded the round) or return (clean round).
  Give-ups raise typed errors carrying the full round history:
  `CapacityExhausted` (a per-cause cap was hit) or `RetryBudgetExceeded`
  (the total budget ran out). Both subclass RuntimeError, so legacy
  callers that caught the old bare RuntimeError still work.
- `FaultPlan` -- seeded deterministic fault injection with named sites,
  wired through the pipeline as trace-compatible static knobs
  (`DAKCConfig.faults`). Each site targets one recovery path; a fault that
  stops firing after `rounds` attempts lets the retry machinery recover a
  run whose histogram is bit-identical to the fault-free run (the CI
  invariant, scripts/ci.sh), while a persistent fault (rounds large)
  drives the give-up errors that were previously unreachable by any test.

Fault sites:

- 'route_drop'   -- drop a seeded fraction of a chunk's routed entries
                    (counted as routing overflow -> slack-doubling retry).
- 'store_drop'   -- drop a seeded fraction of one chunk's store inserts,
                    optionally only past a fill level (counted as store
                    overflow -> rehash retry). Stream receiver only.
- 'hop2_misfit'  -- force the compact hop-2 capacity to 1 slot so the
                    hop-1 fill histogram cannot fit (-> padded fallback).
- 'update_fail'  -- raise `InjectedFault` from the Nth
                    `KmerCounter.update` call, host-side, before anything
                    commits (the preemption drill for checkpoint/restore).
- 'ckpt_write'   -- die mid-file inside a checkpoint write: a partial leaf
                    is left in the staging directory and `InjectedFault`
                    raised before the atomic rename (the stale-.tmp
                    crash-safety drill for train/checkpoint.py).
- 'spill_write'  -- die mid-bin-write inside the spill tier
                    (core/spill.py): a torn segment file is left on disk
                    and `InjectedFault` raised before the manifest commit,
                    so restore must discard it (`fail_after` = segment
                    writes that succeed first).
- 'bin_corrupt'  -- flip bytes inside a sealed (committed) bin segment of
                    bin `bin`; the drain pass must detect the checksum
                    mismatch and raise the typed `spill.SpillCorrupt`.

Round history is bounded: `RetryPolicy.max_history` caps the rounds a
controller keeps (the first round ever plus a ring of the most recent),
so give-up payloads and checkpointed retry state stay O(max_history) no
matter how long an incremental run replays. A controller can be seeded
with prior rounds (`history=`), which is how a restored `KmerCounter`
hands pre-checkpoint rounds to post-restore controllers -- a give-up
after restore carries history spanning the restore boundary. Seeded
rounds never count against `max_rounds` (only rounds this controller
recorded itself do).

Determinism: every in-trace mask is a pure function of (seed, site salt,
element index, chunk index) through the avalanche mixer -- the same plan
produces the same drops on every run, process, and backend.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

import jax.numpy as jnp

from repro.core import owner

# Retry causes -- the three overflow disciplines of the counting pipeline.
ROUTE_SLACK = "route-slack"
STORE_REHASH = "store-rehash"
HOP2_FALLBACK = "hop2-padded-fallback"
CAUSES = (ROUTE_SLACK, STORE_REHASH, HOP2_FALLBACK)

# Named fault sites. The first two are in-trace (seeded masks inside the
# Phase-1 scan); the rest are host-side.
TRACE_SITES = ("route_drop", "store_drop")
SITES = TRACE_SITES + ("hop2_misfit", "update_fail", "ckpt_write",
                       "spill_write", "bin_corrupt")


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounds and growth factors of the one retry engine.

    Hashable and frozen: it rides `DAKCConfig` into the executable-cache
    key. Routing slack doubles and gives up once it EXCEEDS `max_slack`;
    the store grows and gives up once growing would take it past
    `store_cap_ceiling` -- by default (None) the largest store the
    backend's insert kernel holds (`countstore.max_store_capacity`: the
    VMEM bound on the TPU, 2**28 slots elsewhere), so no rehash round ever
    asks for a table that cannot compile; the compact hop 2 falls back to
    the padded tile at most once (there is no third capacity).
    `max_rounds` is a total replay budget across all causes -- a backstop
    against pathological cause ping-pong, set above any legitimate
    doubling ladder (a 1-slot store reaching the ceiling is at most ~28
    rehash rounds). `max_history` caps
    the retained round history (first round + ring of the most recent
    `max_history - 1`); it bounds payload size only, never the budget.
    """
    max_slack: float = 8.0
    slack_growth: float = 2.0
    store_cap_ceiling: Optional[int] = None
    store_growth: int = 2
    max_rounds: int = 40
    max_history: int = 25

    def __post_init__(self):
        if self.max_slack <= 0 or self.slack_growth <= 1:
            raise ValueError(
                f"need max_slack > 0 and slack_growth > 1, got "
                f"{self.max_slack}/{self.slack_growth}")
        if (self.store_cap_ceiling is not None
                and self.store_cap_ceiling < 1) or self.store_growth < 2:
            raise ValueError(
                f"need store_cap_ceiling >= 1 and store_growth >= 2, got "
                f"{self.store_cap_ceiling}/{self.store_growth}")
        if self.max_rounds < 1:
            raise ValueError(f"max_rounds must be >= 1, got {self.max_rounds}")
        if self.max_history < 2:
            raise ValueError(
                f"max_history must be >= 2 (first + at least one recent "
                f"round), got {self.max_history}")

    def store_ceiling(self) -> int:
        """Per-PE store slot ceiling: the explicit `store_cap_ceiling`, or
        the backend's (`countstore.max_store_capacity`)."""
        if self.store_cap_ceiling is not None:
            return self.store_cap_ceiling
        from repro.core import countstore
        return countstore.max_store_capacity()


class RetryRound(NamedTuple):
    """One replayed round, as recorded in error histories and telemetry."""
    round: int                 # 0-based attempt index that overflowed
    causes: Tuple[str, ...]    # which disciplines fired (subset of CAUSES)
    slack: float               # routing slack the round ran at
    store_cap: int             # per-PE store slots the round ran at
    hop2_padded: bool          # whether hop 2 was already on the padded tile
    route_dropped: int
    store_dropped: int
    hop2_dropped: int


class RetryError(RuntimeError):
    """Base of the typed give-up errors; carries the (bounded) round
    history plus the controller's own per-cause replay counts, so a
    caller that escalates instead of dying (the fabsp spill tier) can
    fold the doomed attempt's replays into its lifetime totals."""

    def __init__(self, msg: str, rounds, counts=None):
        super().__init__(msg)
        self.rounds: Tuple[RetryRound, ...] = tuple(rounds)
        self.counts: Dict[str, int] = dict(counts or {})


class CapacityExhausted(RetryError):
    """A per-cause cap was hit (slack past `max_slack` / store past
    `store_cap_ceiling`) while that cause was still dropping entries."""

    def __init__(self, msg: str, cause: str, rounds, counts=None):
        super().__init__(msg, rounds, counts)
        self.cause = cause


class RetryBudgetExceeded(RetryError):
    """The total replay budget (`RetryPolicy.max_rounds`) ran out."""


class RehashInvariantBroken(RetryError):
    """A rehash round dropped live entries -- impossible by construction
    (the grown table is strictly larger than the live-entry count), so
    reaching this means store state corruption, not capacity pressure.
    Raised with the stream's round history and lifetime replay counts
    attached (the same forensic payload as the give-up errors), because
    the history of WHICH rounds grew the store is exactly what debugging
    a broken rehash needs."""

    def __init__(self, msg: str, rounds, counts=None, dropped: int = 0):
        super().__init__(msg, rounds, counts)
        self.dropped = int(dropped)


class InjectedFault(RuntimeError):
    """Raised by host-side fault sites ('update_fail', 'ckpt_write')."""


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """Seeded deterministic fault injection: one named site per plan.

    Hashable and frozen: it rides `DAKCConfig` into executable-cache keys,
    so a faulted round and its clean retry compile as distinct (cached)
    executables.

    site:       one of `SITES` (see module docstring).
    seed:       drives the in-trace drop masks (pure avalanche hash).
    chunk:      chunk index the in-trace sites fire at (-1 = every chunk).
    frac:       fraction of eligible entries dropped at the faulted chunk.
    fill:       'store_drop' only -- fire only once the store holds at
                least this fraction of capacity (storm-at-fill-level).
    rounds:     how many ATTEMPTS of one call/batch the fault fires for.
                1 (default) faults the first round and lets the retry
                recover bit-identically; a large value makes the fault
                persistent, driving the typed give-up errors.
    update_n:   'update_fail' only -- which `KmerCounter.update` call dies.
    fail_after: 'ckpt_write' only -- leaf files written before dying;
                'spill_write' -- bin segment writes that succeed before
                the torn one.
    bin:        'bin_corrupt' only -- which spill bin's sealed segment
                gets its bytes flipped.
    """
    site: str
    seed: int = 0
    chunk: int = 0
    frac: float = 0.5
    fill: float = 0.0
    rounds: int = 1
    update_n: int = 0
    fail_after: int = 0
    bin: int = 0

    def __post_init__(self):
        if self.site not in SITES:
            raise ValueError(f"unknown fault site {self.site!r}; "
                             f"sites are {SITES}")
        if not 0.0 < self.frac <= 1.0:
            raise ValueError(f"frac must be in (0, 1], got {self.frac}")
        if not 0.0 <= self.fill < 1.0:
            raise ValueError(f"fill must be in [0, 1), got {self.fill}")
        if self.rounds < 1 or self.update_n < 0 or self.fail_after < 0 \
                or self.bin < 0:
            raise ValueError(
                "rounds must be >= 1; update_n/fail_after/bin >= 0")

    def fires(self, attempt: int) -> bool:
        """Whether the fault is armed for the given 0-based attempt."""
        return attempt < self.rounds


def active_trace_fault(plan: Optional[FaultPlan],
                       attempt: int) -> Optional[FaultPlan]:
    """The plan, iff it has an in-trace site armed for this attempt."""
    if plan is not None and plan.site in TRACE_SITES and plan.fires(attempt):
        return plan
    return None


# Per-site salts decorrelate the drop masks of different sites sharing one
# seed (golden-ratio / murmur odd constants, same family as core/owner.py).
_SITE_SALT = {"route_drop": 0x9E3779B9, "store_drop": 0x85EBCA6B}


def fault_mask(n: int, plan: FaultPlan, chunk_idx) -> jnp.ndarray:
    """(n,) bool: the seeded deterministic drop mask of an in-trace site.

    `chunk_idx` is the traced scan counter; the mask is nonzero only at the
    plan's chunk (or every chunk for chunk=-1). Element selection is a pure
    avalanche hash of (seed, site, index) thresholded at `frac`, so the
    same plan drops the same entries on every run.
    """
    idx = jnp.arange(n, dtype=jnp.uint32)
    salt = jnp.uint32((plan.seed * 0x9E3779B9 + _SITE_SALT[plan.site])
                      & 0xFFFFFFFF)
    h = owner._mix32(idx ^ salt)
    thresh = jnp.uint32(min(int(plan.frac * 4294967296.0), 4294967295))
    hit = h < thresh
    if plan.chunk >= 0:
        hit = hit & (jnp.int32(chunk_idx) == jnp.int32(plan.chunk))
    return hit


class RetryController:
    """Driver state of one retried call (or one `KmerCounter` batch).

    The call site owns the loop; the controller owns the policy arithmetic:

        ctrl = RetryController(policy, slack=cfg.slack, store_cap=cap)
        while True:
            ... run one attempt at (ctrl.slack, ctrl.store_cap,
                ctrl.hop2_padded) ...
            if not ctrl.observe(route_dropped=r, store_dropped=s,
                                hop2_dropped=h):
                break   # clean round: the attempt's result is final

    `observe` returns the tuple of causes that fired (empty = clean),
    after growing the corresponding knobs and recording the round; it
    raises `CapacityExhausted` / `RetryBudgetExceeded` -- with the
    (bounded) history attached -- instead of growing past a cap.

    History is a first-plus-ring structure: the first round ever recorded
    (or seeded via `history=`) is pinned, and the most recent
    `max_history - 1` rounds ride a ring buffer; middle rounds of a long
    ladder age out. `rounds` materializes the retained rounds as a list.
    Seeded history rides into error payloads but never counts against
    `max_rounds` -- only `own_rounds` (rounds recorded by this
    controller) can exhaust the budget.
    """

    def __init__(self, policy: RetryPolicy, *, slack: float, store_cap: int,
                 hop2_padded: bool = True,
                 history: Iterable[RetryRound] = ()):
        self.policy = policy
        self.slack = slack
        self.store_cap = store_cap
        self.hop2_padded = hop2_padded
        self.attempts = 0                      # completed attempts
        self.own_rounds = 0                    # dirty rounds recorded here
        self.counts: Dict[str, int] = {c: 0 for c in CAUSES}
        self._first: Optional[RetryRound] = None
        self._tail = collections.deque(maxlen=policy.max_history - 1)
        for r in history:
            self._record(RetryRound(*r))

    def _record(self, r: RetryRound) -> None:
        if self._first is None:
            self._first = r
        else:
            self._tail.append(r)   # ring: oldest non-first round ages out

    @property
    def rounds(self) -> List[RetryRound]:
        """Retained round history (first + most recent), oldest first."""
        head = [self._first] if self._first is not None else []
        return head + list(self._tail)

    def observe(self, *, route_dropped: int = 0, store_dropped: int = 0,
                hop2_dropped: int = 0) -> Tuple[str, ...]:
        causes = []
        if route_dropped > 0:
            causes.append(ROUTE_SLACK)
        if store_dropped > 0:
            causes.append(STORE_REHASH)
        if hop2_dropped > 0:
            causes.append(HOP2_FALLBACK)
        attempt = self.attempts
        self.attempts += 1
        if not causes:
            return ()
        self._record(RetryRound(
            round=attempt, causes=tuple(causes), slack=self.slack,
            store_cap=self.store_cap, hop2_padded=self.hop2_padded,
            route_dropped=route_dropped, store_dropped=store_dropped,
            hop2_dropped=hop2_dropped))
        self.own_rounds += 1
        if ROUTE_SLACK in causes and self.slack > self.policy.max_slack:
            raise CapacityExhausted(
                f"routing overflow persists at slack {self.slack} "
                f"(> max_slack {self.policy.max_slack}): {route_dropped} "
                f"entries dropped after {self.own_rounds} round(s)",
                ROUTE_SLACK, self.rounds, self.counts)
        ceiling = self.policy.store_ceiling()
        if STORE_REHASH in causes \
                and self.store_cap * self.policy.store_growth > ceiling:
            raise CapacityExhausted(
                f"count store still overflows at {self.store_cap} slots "
                f"(growing past ceiling {ceiling}): "
                f"{store_dropped} inserts dropped after "
                f"{self.own_rounds} round(s)", STORE_REHASH, self.rounds,
                self.counts)
        if self.own_rounds >= self.policy.max_rounds:
            raise RetryBudgetExceeded(
                f"retry budget exhausted after {self.own_rounds} replayed "
                f"rounds (max_rounds={self.policy.max_rounds}); last causes "
                f"{tuple(causes)}", self.rounds, self.counts)
        for c in causes:
            self.counts[c] += 1
        if STORE_REHASH in causes:
            self.store_cap *= self.policy.store_growth
        if ROUTE_SLACK in causes:
            self.slack *= self.policy.slack_growth
        if HOP2_FALLBACK in causes:
            self.hop2_padded = True
        return tuple(causes)


def rounds_to_json(rounds: Iterable[RetryRound]) -> List[list]:
    """Round history as JSON-serializable lists (checkpoint `extra`)."""
    return [list(r) for r in rounds]


def rounds_from_json(data) -> List[RetryRound]:
    """Inverse of `rounds_to_json` (tuple-ness of `causes` restored)."""
    out = []
    for row in data or []:
        r = RetryRound(*row)
        out.append(r._replace(causes=tuple(r.causes)))
    return out
