"""Logical-client population for cross-device federation.

The reference (and every PR up to 5) federates a handful of always-on
"clients" that ARE the device slots — cross-silo at toy scale. Real
cross-device federation (FedJAX, arxiv 2108.02117) trains a population of
N >> devices *logical* clients: each round a cohort is sampled onto the
fixed mesh, trains its own data shard, and reports — or doesn't. This
module is the host-side client-state layer behind ``fed.population``:

* :class:`ClientPopulation` — N logical clients, each owning

  - a **data-shard handle**: a static, seeded, equal-size row shard of the
    training set (equal sizes keep the per-round step count static);
  - a **sample count** (the ``weighted`` sampler's selection weight);
  - an **optimizer sidecar** where the strategy keeps one
    (``client_state="persist"``): the non-parameter slot leaves — optax
    states, PRNG key, step counter, decoupled-mode grad accumulator —
    written back when the client rotates out of its slot and reloaded on
    its next selection. Kept host-side in an LRU-bounded dict and spilled
    to disk above ``resident_cap`` (``spill_dir``), so population size is
    bounded by disk, not host RAM;
  - a **participation ledger** row: selected / reported / dropped /
    deadline-cut counters plus the quarantine expiry, serialized into
    snapshots so a resumed run continues the identical schedule.

* :class:`CohortPlan` + :func:`build_cohort_plan` — one round's resolved
  cohort: ``ceil(slots * over_select)`` sampled candidates
  (priority-ordered), the chaos-simulated dropouts removed, the survivors
  packed front-to-back into the device slots, short cohorts padded by
  repeating survivors with weight 0 (static shapes; pads never write
  back).

* :func:`plan_round_weights` — one round's per-slot participation
  weights: 0 for pads, per-round dropouts, and clients whose simulated
  report latency exceeds the round deadline (the deadline-cut). The same
  ``(seed, round, attempt, client)`` derivation as the packing step, so
  the two views of a client's fate can never disagree.

* :exc:`QuorumFailure` — raised when a round's reporting count falls
  below ``min_reports``; the Trainer discards the round from its entry
  state and replays with a fresh draw (``attempt`` + 1), bounded by
  ``quorum_retries``.

Everything here is host-side numpy: the device program is untouched — a
sampled-world round compiles to exactly the fixed-world program, fed a
different batch stack and weight vector.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Iterator

import numpy as np

# the non-parameter ClientState slot leaves that follow a LOGICAL client
# across selections: optax states, PRNG key, step counter, decoupled-mode
# grad accumulator, and the update codec's error-feedback residual
# (fed.dcn_compress sign1bit/topk — a healed or fresh client starts from
# the all-zero template residual, same contract as the optimizer moments)
SIDECAR_FIELDS = (
    "step", "opt_user", "opt_news", "rng", "news_grad_accum", "ef_residual",
)


class QuorumFailure(Exception):
    """A round's reporting cohort fell below ``fed.population.min_reports``.

    Control flow, not an error: the Trainer catches it BEFORE any state
    mutation (weights are computed at round entry), counts a quorum
    replay, and re-enters the round with ``attempt + 1`` — a fresh cohort
    draw and fresh fault dice — up to ``quorum_retries`` times, after
    which the run aborts with an operator-grade message.
    """

    def __init__(self, round_idx: int, reporting: int, min_reports: int,
                 attempt: int):
        super().__init__(
            f"round {round_idx}: {reporting} reporting clients < quorum "
            f"min_reports={min_reports} (draw attempt {attempt})"
        )
        self.round_idx = int(round_idx)
        self.reporting = int(reporting)
        self.attempt = int(attempt)


@dataclass
class CohortPlan:
    """One round's resolved cohort."""

    round_idx: int                     # the round the cohort was drawn for
    attempt: int                       # quorum re-draw counter
    sampled: np.ndarray                # (S,) drawn candidates, priority order
    start_dropped: np.ndarray          # sampled ids that never started
    slot_clients: np.ndarray           # (slots,) logical occupant per slot
    slot_real: np.ndarray              # (slots,) bool; False = weight-0 pad

    @property
    def spares_unused(self) -> int:
        """Over-selected survivors that found no free slot."""
        survivors = len(self.sampled) - len(self.start_dropped)
        return max(0, survivors - int(self.slot_real.sum()))


def build_cohort_plan(
    sampler: Any,
    slots: int,
    round_idx: int,
    over_select: float,
    chaos: Any = None,
    exclude: set | tuple = (),
    attempt: int = 0,
    pack: bool = True,
) -> CohortPlan:
    """Sample and pack one round's cohort (see module docstring).

    ``pack=False`` is the fixed-world (population == slots) mode: slots
    ARE the clients, so over-selection repacking is skipped — a dropout
    keeps its slot and loses its weight in :func:`plan_round_weights`
    instead.
    """
    if over_select < 1.0:
        raise ValueError(
            f"fed.population.over_select must be >= 1.0, got {over_select}"
        )
    from fedrec_tpu.fed.chaos import population_report

    want = int(np.ceil(slots * over_select))
    sampled = sampler.draw(round_idx, want, exclude=exclude, attempt=attempt)
    if sampled.size == 0:
        raise RuntimeError(
            "cohort sampling found no eligible clients (population "
            "exhausted by quarantine?)"
        )
    if not pack:
        return CohortPlan(
            round_idx=int(round_idx),
            attempt=int(attempt),
            sampled=np.asarray(sampled, np.int64),
            start_dropped=np.zeros((0,), np.int64),
            slot_clients=np.resize(sampled, slots).astype(np.int64),
            slot_real=np.arange(slots) < len(sampled),
        )
    dropped, _ = population_report(chaos, round_idx, sampled, attempt)
    survivors = sampled[~dropped]
    if survivors.size == 0:
        # everyone sampled dropped: pad slots from the raw draw so shapes
        # stay static; every slot is weight-0 and the quorum policy (or
        # the zero-participation round contract) decides what happens
        occupants = sampled[:1]
    else:
        occupants = survivors[:slots]
    n_real = int(min(len(occupants), slots)) if survivors.size else 0
    slot_clients = np.resize(occupants, slots).astype(np.int64)
    slot_real = np.arange(slots) < n_real
    return CohortPlan(
        round_idx=int(round_idx),
        attempt=int(attempt),
        sampled=np.asarray(sampled, np.int64),
        start_dropped=np.asarray(sampled[dropped], np.int64),
        slot_clients=slot_clients,
        slot_real=slot_real,
    )


def plan_round_weights(
    plan: CohortPlan,
    round_idx: int,
    deadline_ms: float = 0.0,
    chaos: Any = None,
) -> tuple[np.ndarray, dict]:
    """(slots,) float32 participation weights for ``round_idx`` under
    ``plan``'s packing, plus an event dict for the ledger/metrics:
    ``{"reported": ids, "dropped": ids, "deadline_cut": ids}``.

    For the plan's own round the dropout draws REPLAY the packing
    draws (same rng keys), so an occupant can only lose weight to the
    deadline.
    """
    from fedrec_tpu.fed.chaos import population_report

    slots = plan.slot_clients.shape[0]
    dropped, latency = population_report(
        chaos, round_idx, plan.slot_clients, plan.attempt
    )
    w = plan.slot_real & ~dropped
    cut = np.zeros(slots, bool)
    if deadline_ms and deadline_ms > 0:
        cut = w & (latency > deadline_ms)
        w = w & ~cut
    # a client padded into several slots must count (and weigh) once —
    # dedupe by first slot occurrence; pads are weight 0 anyway via
    # slot_real, so this only guards the degenerate everyone-dropped fill
    events = {
        "reported": _unique_ids(plan.slot_clients[w]),
        "dropped": _unique_ids(plan.slot_clients[plan.slot_real & dropped]),
        "deadline_cut": _unique_ids(plan.slot_clients[cut]),
    }
    return w.astype(np.float32), events


def _unique_ids(ids: np.ndarray) -> np.ndarray:
    return np.unique(np.asarray(ids, np.int64))


# --------------------------------------------------------------- ledger
class ParticipationLedger:
    """Per-logical-client participation bookkeeping + quarantine expiry."""

    def __init__(self, population: int):
        self.population = int(population)
        self.selected = np.zeros((population,), np.int64)
        self.reported = np.zeros((population,), np.int64)
        self.dropped = np.zeros((population,), np.int64)
        self.deadline_cut = np.zeros((population,), np.int64)
        # client id -> first round it may be sampled again
        self.quarantined: dict[int, int] = {}

    def commit(self, cohort: np.ndarray, events: dict) -> None:
        np.add.at(self.selected, np.asarray(cohort, np.int64), 1)
        for key, arr in (
            ("reported", self.reported),
            ("dropped", self.dropped),
            ("deadline_cut", self.deadline_cut),
        ):
            ids = np.asarray(events.get(key, ()), np.int64)
            if ids.size:
                np.add.at(arr, ids, 1)

    def quarantine(self, client_id: int, until_round: int) -> None:
        cid = int(client_id)
        self.quarantined[cid] = max(self.quarantined.get(cid, 0), int(until_round))

    def active_quarantine(self, round_idx: int) -> set[int]:
        """Clients still excluded at ``round_idx`` (expired entries pruned)."""
        expired = [c for c, until in self.quarantined.items()
                   if until <= round_idx]
        for c in expired:
            del self.quarantined[c]
        return set(self.quarantined)

    def coverage(self) -> float:
        """Fraction of the population selected at least once."""
        return float((self.selected > 0).mean())

    # -------------------------------------------------------- persistence
    def state_dict(self) -> dict:
        q_ids = np.asarray(sorted(self.quarantined), np.int64)
        return {
            "population": np.int64(self.population),
            "selected": self.selected.copy(),
            "reported": self.reported.copy(),
            "dropped": self.dropped.copy(),
            "deadline_cut": self.deadline_cut.copy(),
            "quarantine_ids": q_ids,
            "quarantine_until": np.asarray(
                [self.quarantined[int(c)] for c in q_ids], np.int64
            ),
        }

    def load_state_dict(self, state: dict, resize: bool = False) -> None:
        """Restore the ledger. ``resize=False`` (the default) demands an
        exact population match — a mismatch on a fixed-world resume is a
        config error. ``resize=True`` is the elastic-membership continuity
        mode: a sidecar saved under a DIFFERENT population size is adopted
        by copying the overlapping prefix of every counter (clients beyond
        the saved population start their history fresh; counters for
        clients that no longer exist are dropped) and keeping only the
        quarantine entries still addressable — participation history
        survives an epoch's slot rebalance instead of resetting to zero.
        """
        pop = int(state["population"])
        if pop != self.population and not resize:
            raise ValueError(
                f"ledger population mismatch: saved {pop} vs configured "
                f"{self.population}"
            )
        n = min(pop, self.population)
        for key in ("selected", "reported", "dropped", "deadline_cut"):
            arr = np.asarray(state[key], np.int64)
            if arr.shape != (pop,):
                raise ValueError(f"ledger {key} shape {arr.shape}")
            fresh = np.zeros((self.population,), np.int64)
            fresh[:n] = arr[:n]
            setattr(self, key, fresh)
        ids = np.asarray(state.get("quarantine_ids", ()), np.int64)
        until = np.asarray(state.get("quarantine_until", ()), np.int64)
        self.quarantined = {
            int(c): int(u)
            for c, u in zip(ids.reshape(-1), until.reshape(-1))
            if int(c) < self.population
        }


# ----------------------------------------------------------- population
class ClientPopulation:
    """N logical clients: data shards, sidecar store, ledger.

    ``shard_rows(i)`` is client *i*'s static row shard of the (local)
    training set: a seeded permutation dealt round-robin and truncated to
    the common ``shard_size = n_rows // N`` — equal sizes by construction
    (the static-step-count contract), disjoint, deterministic in
    ``(data_seed, N)``.
    """

    def __init__(
        self,
        num_clients: int,
        num_rows: int,
        data_seed: int = 0,
        batch_size: int = 0,
        resident_cap: int = 0,
        spill_dir: str | Path | None = None,
    ):
        if num_clients <= 0:
            raise ValueError(f"population num_clients must be > 0, got {num_clients}")
        self.num_clients = int(num_clients)
        self.num_rows = int(num_rows)
        self.data_seed = int(data_seed)
        self.shard_size = self.num_rows // self.num_clients
        if self.shard_size < 1:
            raise ValueError(
                f"population of {num_clients} clients over {num_rows} "
                "training rows leaves empty shards; shrink "
                "fed.population.num_clients or bring more data"
            )
        if batch_size and self.shard_size < batch_size:
            raise ValueError(
                f"per-client shard ({self.shard_size} rows = {num_rows} // "
                f"{num_clients}) is smaller than data.batch_size="
                f"{batch_size}: a selected client could not fill one step. "
                "Shrink the batch size or the population."
            )
        perm = np.random.default_rng([self.data_seed, 0x909]).permutation(
            self.num_rows
        )
        # round-robin deal, truncated to the common size, sorted for
        # locality of the underlying row gathers
        self._rows = np.stack([
            np.sort(perm[i :: self.num_clients][: self.shard_size])
            for i in range(self.num_clients)
        ])
        self.sample_counts = np.full((self.num_clients,), self.shard_size, np.int64)
        self.ledger = ParticipationLedger(self.num_clients)
        # sidecar store: cid -> list of host leaves; LRU above resident_cap
        self.resident_cap = int(resident_cap)
        self.spill_dir = Path(spill_dir) if spill_dir else None
        self._resident: OrderedDict[int, list] = OrderedDict()
        self._spilled: set[int] = set()
        self._treedef = None
        self.spill_count = 0
        # per-client indexed.take views — static per (indexed, cid), so
        # rebuilding them every epoch of every round is pure host latency
        # between device dispatches; LRU-bounded to a few cohorts' worth
        self._take_cache: OrderedDict[int, Any] = OrderedDict()
        self._take_cache_src: int | None = None

    # ------------------------------------------------------------- shards
    def shard_rows(self, client_id: int) -> np.ndarray:
        return self._rows[int(client_id)]

    def steps_per_epoch(self, batch_size: int) -> int:
        return max(self.shard_size // int(batch_size), 1)

    def client_seed(self, client_id: int) -> int:
        """Stable per-client batcher seed (shuffle + negative sampling)."""
        return (self.data_seed * 1_000_003 + 0x5EED + int(client_id)) % (2**31)

    def cohort_epoch_batches(
        self, cohort: np.ndarray, indexed: Any, data_cfg: Any, epoch_idx: int
    ) -> Iterator[Any]:
        """Stacked (slots, B, ...) batches where slot *j* iterates client
        ``cohort[j]``'s OWN shard — the cross-device replacement for
        ``TrainBatcher.epoch_batches_sharded``'s epoch-resharding of the
        whole corpus. Per-client order and negatives are keyed by
        ``(client_seed, epoch_idx)``, so a client revisited in a later
        round reshuffles, and the schedule is reproducible without any
        per-client visit counters (resume-friendly)."""
        from fedrec_tpu.data.batcher import Batch, TrainBatcher

        cohort = np.asarray(cohort, np.int64)
        iters = [
            TrainBatcher(
                self._client_view(int(cid), indexed, cap=4 * len(cohort)),
                data_cfg.batch_size,
                data_cfg.npratio,
                shuffle=data_cfg.shuffle,
                drop_remainder=True,
                seed=self.client_seed(cid),
            ).epoch_batches(epoch_idx)
            for cid in cohort
        ]
        for _ in range(self.steps_per_epoch(data_cfg.batch_size)):
            bs = [next(it) for it in iters]
            yield Batch(
                candidates=np.stack([b.candidates for b in bs]),
                history=np.stack([b.history for b in bs]),
                his_len=np.stack([b.his_len for b in bs]),
                labels=np.stack([b.labels for b in bs]),
            )

    def _client_view(self, cid: int, indexed: Any, cap: int) -> Any:
        """LRU-cached ``indexed.take(shard_rows(cid))`` (invalidated if a
        different ``indexed`` object arrives — one population serves one
        training set)."""
        if self._take_cache_src is not id(indexed):
            self._take_cache.clear()
            self._take_cache_src = id(indexed)
        view = self._take_cache.get(cid)
        if view is None:
            view = indexed.take(self.shard_rows(cid))
            self._take_cache[cid] = view
        else:
            self._take_cache.move_to_end(cid)
        while len(self._take_cache) > max(int(cap), 8):
            self._take_cache.popitem(last=False)
        return view

    # ----------------------------------------------------------- sidecars
    def _spill_path(self, client_id: int) -> Path:
        assert self.spill_dir is not None
        return self.spill_dir / f"client_{int(client_id):08d}.npz"

    def put_sidecar(self, client_id: int, sidecar: Any) -> None:
        """Store a client's sidecar pytree (host arrays), evicting the
        least-recently-stored resident to disk above ``resident_cap``."""
        import jax

        cid = int(client_id)
        leaves, treedef = jax.tree_util.tree_flatten(sidecar)
        if self._treedef is None:
            self._treedef = treedef
        elif treedef != self._treedef:
            raise ValueError("sidecar pytree structure changed mid-run")
        self._resident[cid] = [np.asarray(x) for x in leaves]
        self._resident.move_to_end(cid)
        self._spilled.discard(cid)
        if self.resident_cap > 0:
            while len(self._resident) > self.resident_cap:
                old_cid, old_leaves = self._resident.popitem(last=False)
                self._spill(old_cid, old_leaves)

    def _spill(self, cid: int, leaves: list) -> None:
        if self.spill_dir is None:
            raise ValueError(
                "fed.population.resident_cap is set but no spill_dir is "
                "available (set fed.population.spill_dir or a snapshot dir)"
            )
        self.spill_dir.mkdir(parents=True, exist_ok=True)
        tmp = self._spill_path(cid).with_suffix(".npz.tmp")
        with open(tmp, "wb") as f:  # handle: np.savez would append .npz
            np.savez(f, **{f"leaf_{i}": x for i, x in enumerate(leaves)})
        tmp.replace(self._spill_path(cid))
        self._spilled.add(cid)
        self.spill_count += 1

    def get_sidecar(self, client_id: int) -> Any | None:
        """The client's stored sidecar pytree, or None if it was never
        stored (first selection: the caller supplies the template)."""
        import jax

        cid = int(client_id)
        if cid in self._resident:
            self._resident.move_to_end(cid)
            leaves = self._resident[cid]
            return jax.tree_util.tree_unflatten(self._treedef, list(leaves))
        if cid in self._spilled:
            with np.load(self._spill_path(cid)) as z:
                leaves = [z[f"leaf_{i}"] for i in range(len(z.files))]
            return jax.tree_util.tree_unflatten(self._treedef, leaves)
        return None

    def reset_sidecar(self, client_id: int) -> None:
        """Forget a client's stored sidecar (quarantine healing: its next
        selection restarts from the template)."""
        cid = int(client_id)
        self._resident.pop(cid, None)
        if cid in self._spilled:
            self._spilled.discard(cid)
            try:
                self._spill_path(cid).unlink()
            except OSError:
                pass

    @property
    def resident_sidecars(self) -> int:
        return len(self._resident)
