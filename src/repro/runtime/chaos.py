"""Deterministic fault injection for the serving stack.

Robustness claims that were never exercised are wishes.  This module
makes the failure modes the serving stack defends against — stragglers,
poisoned model updates, residency thrash, overload bursts — injectable
on a SCRIPT, so a test (or benchmarks/fault_sweep.py, or
``launch/serve.py --chaos``) can replay the exact same failure sequence
twice and assert the RequestResult stream is bit-identical.

Two pieces:

  * ``ChaosPlan`` — the script: which drain ticks stall (straggler), by
    what factor; which (tick, tenant) pairs receive a NaN-poisoned
    ``ModelStore.update``; which ticks evict every resident tenant
    (eviction storm); which ticks receive an arrival burst on top of the
    base trace.  Generated from a seed (``ChaosPlan.generate``), or one
    of the named ``PRESETS``; JSON round-trips for committed CI traces.
  * ``ChaosInjector`` — the hand on the levers: ``attach(scheduler)``
    replaces the scheduler's wall clock with a virtual one (each launch
    costs ``base_batch_time``, straggler ticks cost ``factor`` times
    that), so ``batch_time`` and ``queue_s`` — and therefore the
    StepTimer's watch/checkpoint/evict verdicts and every downstream
    degrade decision — are a pure function of the plan.
    ``replay_trace(..., chaos=injector)`` calls
    ``extra_arrivals``/``apply`` at each tick.

Every injected fault lands as a typed ``chaos_*`` event in the
scheduler's event stream, interleaved with the shed/degrade/breaker
events it provokes — one totally-ordered record of cause and effect.

A NaN injection that the store ACCEPTS is itself a test failure: the
injector raises rather than let a poisoned generation serve, which is
exactly the invariant (model_store health check) CI pins.
"""
from __future__ import annotations

import copy
import json
from dataclasses import asdict, dataclass
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.events import event
from repro.serving.model_store import PoisonedParamsError


@dataclass(frozen=True)
class ChaosPlan:
    """A seeded, serializable fault script over ``ticks`` drain ticks."""

    seed: int = 0
    ticks: int = 64
    #: drain ticks whose launch wall-time is inflated
    straggler_ticks: Tuple[int, ...] = ()
    straggler_factor: float = 8.0
    #: (tick, tenant_index) pairs: poison that tenant's next update
    nan_events: Tuple[Tuple[int, int], ...] = ()
    #: ticks on which every resident tenant is evicted
    storm_ticks: Tuple[int, ...] = ()
    #: tick -> extra arrivals injected on top of the base trace
    burst: Tuple[Tuple[int, int], ...] = ()

    def burst_at(self, tick: int) -> int:
        for t, n in self.burst:
            if t == tick:
                return n
        return 0

    # ------------------------------------------------------------ codecs

    def to_json(self) -> str:
        return json.dumps(asdict(self), sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "ChaosPlan":
        raw = json.loads(text)
        for key in ("straggler_ticks", "storm_ticks"):
            raw[key] = tuple(int(t) for t in raw.get(key, ()))
        raw["nan_events"] = tuple((int(t), int(i))
                                  for t, i in raw.get("nan_events", ()))
        raw["burst"] = tuple((int(t), int(n))
                             for t, n in raw.get("burst", ()))
        return cls(**raw)

    # --------------------------------------------------------- generator

    @classmethod
    def generate(cls, *, seed: int = 0, ticks: int = 64,
                 n_stragglers: int = 0, straggler_factor: float = 8.0,
                 n_nan: int = 0, n_tenants: int = 0, n_storms: int = 0,
                 n_bursts: int = 0, burst_size: int = 64) -> "ChaosPlan":
        """A deterministic plan from a seed: fault ticks are sampled
        without replacement PER FAULT CLASS (a tick can carry a burst
        AND a straggler — compound faults are the interesting ones)."""
        rng = np.random.default_rng(seed)

        def pick(n):
            n = min(int(n), ticks)
            # keep the first ticks clean so warmup baselines calibrate
            lo = min(8, ticks // 4)
            return tuple(sorted(int(t) for t in rng.choice(
                np.arange(lo, ticks), size=n, replace=False))) if n else ()

        nan_events = tuple((t, int(rng.integers(0, max(1, n_tenants))))
                           for t in pick(n_nan))
        return cls(seed=seed, ticks=ticks,
                   straggler_ticks=pick(n_stragglers),
                   straggler_factor=float(straggler_factor),
                   nan_events=nan_events, storm_ticks=pick(n_storms),
                   burst=tuple((t, int(burst_size))
                               for t in pick(n_bursts)))

    @classmethod
    def preset(cls, name: str, *, seed: int = 0, ticks: int = 64,
               n_tenants: int = 0) -> "ChaosPlan":
        try:
            kw = dict(PRESETS[name])
        except KeyError:
            raise ValueError(f"unknown chaos preset {name!r} "
                             f"(known: {sorted(PRESETS)})") from None
        return cls.generate(seed=seed, ticks=ticks, n_tenants=n_tenants,
                            **kw)


#: named fault mixes for CI and --chaos NAME
PRESETS: Dict[str, Dict] = {
    # overload only: arrival bursts several times the per-drain capacity
    "burst": {"n_bursts": 4, "burst_size": 96},
    # slow silicon: inflated launch times trip the straggler escalation
    "straggler": {"n_stragglers": 12, "straggler_factor": 8.0},
    # sick tenants + residency churn (store-mode schedulers)
    "storm": {"n_nan": 4, "n_storms": 4, "n_bursts": 2, "burst_size": 48},
    # everything at once — the committed fault_sweep/CI trace
    "mixed": {"n_bursts": 4, "burst_size": 96, "n_stragglers": 8,
              "straggler_factor": 8.0, "n_nan": 3, "n_storms": 2},
}


def _poison_first_leaf(params):
    """Params with a NaN written into the first float leaf — the minimal
    corruption a crashed trainer or a truncated checkpoint produces."""
    done = [False]

    def one(leaf):
        if not done[0] and hasattr(leaf, "dtype") \
                and jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
            done[0] = True
            flat = jnp.ravel(jnp.asarray(leaf)).at[0].set(jnp.nan)
            return flat.reshape(jnp.asarray(leaf).shape)
        return leaf

    poisoned = jax.tree.map(one, params)
    assert done[0], "no float leaf to poison"
    return poisoned


class ChaosInjector:
    """Executes a ``ChaosPlan`` against one scheduler replay.

    ``base_batch_time`` is the virtual wall-time of one healthy launch
    (seconds); straggler ticks cost ``plan.straggler_factor`` times
    that.  The virtual clock is parity-toggled: the scheduler reads it
    once before and once after each launch, so odd reads return the
    accumulated time and even reads add the launch's scripted cost.  The
    read that stamps a submit returns the accumulated time and leaves the
    parity alone."""

    def __init__(self, plan: ChaosPlan, *, store=None,
                 base_batch_time: float = 1e-3):
        self.plan = plan
        self.store = store
        self.base_batch_time = float(base_batch_time)
        self.sched = None
        self._vt = 0.0
        self._in_launch = False
        self._submitting = False
        self._stragglers = frozenset(plan.straggler_ticks)
        self._storms = frozenset(plan.storm_ticks)
        self._nan_by_tick: Dict[int, List[int]] = {}
        for t, idx in plan.nan_events:
            self._nan_by_tick.setdefault(int(t), []).append(int(idx))
        self.injected: Dict[str, int] = {"straggler": 0, "nan": 0,
                                         "storm": 0, "burst": 0}

    # ------------------------------------------------------------- clock

    def attach(self, scheduler) -> "ChaosInjector":
        """Install the virtual clock; the plan owns time from here on."""
        self.sched = scheduler
        scheduler.clock = self._clock
        submit = scheduler.submit

        def stamped_submit(*args, **kwargs):
            self._submitting = True
            try:
                return submit(*args, **kwargs)
            finally:
                self._submitting = False

        scheduler.submit = stamped_submit
        return self

    def _clock(self) -> float:
        if self._submitting:
            return self._vt
        if not self._in_launch:
            self._in_launch = True
            return self._vt
        self._in_launch = False
        factor = 1.0
        # the scheduler bumped .tick before the launch, so the CURRENT
        # tick is the one the plan scripts
        if self.sched is not None and self.sched.tick in self._stragglers:
            factor = self.plan.straggler_factor
            self.injected["straggler"] += 1
            self.sched.events.append(event(
                "chaos_straggler", self.sched.tick, "chaos",
                factor=factor))
        self._vt += self.base_batch_time * factor
        return self._vt

    # ------------------------------------------------------------ faults

    def extra_arrivals(self, tick: int) -> int:
        n = self.plan.burst_at(tick)
        if n and self.sched is not None:
            self.injected["burst"] += 1
            self.sched.events.append(event("chaos_burst", tick, "chaos",
                                           n=n))
        return n

    def apply(self, scheduler, tick: int) -> None:
        """Inject this tick's store-level faults (no-op without a
        store): NaN-poisoned tenant updates — which the store MUST
        reject (PoisonedParamsError), feeding the tenant's circuit
        breaker — and eviction storms."""
        if self.store is None:
            return
        for idx in self._nan_by_tick.get(tick, ()):
            mids = self.store.model_ids
            if not mids:
                continue
            mid = mids[idx % len(mids)]
            bad = copy.copy(self.store.template)
            _gen, params = self.store.params_of(mid)
            bad._params = _poison_first_leaf(params)
            self.injected["nan"] += 1
            scheduler.events.append(event("chaos_nan", tick, "chaos",
                                          model=str(mid)))
            try:
                self.store.update(mid, bad)
            except PoisonedParamsError as e:
                scheduler.events.append(event(
                    "nan_rejected", tick, "scheduler", model=str(mid),
                    leaf=e.leaf_path))
                scheduler.record_failure(mid, reason="nan_rejected")
            else:
                raise AssertionError(
                    f"NaN-poisoned update for tenant {mid!r} was ACCEPTED "
                    f"by the store — the health-check invariant is broken")
        if tick in self._storms:
            n = 0
            for mid in list(self.store.resident_ids):
                self.store.evict(mid)
                n += 1
            self.injected["storm"] += 1
            scheduler.events.append(event("chaos_eviction_storm", tick,
                                          "chaos", evicted=n))
