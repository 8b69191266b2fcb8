"""Batched serving engines.

``ServeEngine`` — LM prefill + decode loop over a KV/SSM cache.  The engine
jit-compiles one prefill step and one decode step per (batch, seq) bucket
and runs greedy or temperature sampling. Aligned decode (all sequences at
the same position) is the fast path used by the assigned decode shapes;
ragged continuous batching falls back to per-sequence scatter.

``NonNeuralServeEngine`` — serving for ANY estimator registered in
``core/estimator.py`` (kNN, K-Means, GNB, GMM, RF): request batches are
padded to power-of-two buckets (so at most log2(max_batch) jit
specialisations exist per algorithm) and each bucket runs the estimator's
registry-dispatched batch path as one launch; batches beyond ``max_batch``
are microbatched.  ``KNNServeEngine`` survives as the kNN-typed facade.
"""
from __future__ import annotations

import copy as _copy
import functools
import itertools
import time as _time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ServeConfig
from repro.core import cluster as _cluster
from repro.core import knn as _knn
from repro.core.estimator import Estimator, KNNEstimator
from repro.kernels import dispatch
from repro.models import transformer


@dataclass
class ClassifyResult:
    classes: jnp.ndarray       # (B,) int32 prediction per query
    aux: jnp.ndarray           # (B, ...) algorithm evidence (see estimator)
    launches: int              # kernel launches used for this request
    algorithm: str = "knn"     # which estimator produced this result

    @property
    def neighbors(self) -> jnp.ndarray:
        """kNN back-compat alias: aux is the (B, k) neighbour indices."""
        if self.algorithm != "knn":
            raise AttributeError(
                f"ClassifyResult.neighbors is kNN-only (aux = neighbour "
                f"indices); this result came from {self.algorithm!r}, whose "
                f"aux is its own evidence — use .aux (see "
                f"Estimator.empty_aux for the per-algorithm shape)")
        return self.aux


@dataclass
class GroupClassifyResult:
    """One grouped (multi-tenant) launch: per-tenant rows of predictions
    and evidence, sliced back to the caller's (G, B) from the padded
    (group-bucket, bucket) launch shape."""
    classes: jnp.ndarray       # (G, B) int32 prediction per tenant x query
    aux: jnp.ndarray           # (G, B, ...) per-tenant algorithm evidence
    launches: int              # vmapped kernel launches used
    algorithm: str = "knn"


# distinguishes two engines for result-cache keying even when they wrap
# the same estimator (serving/scheduler.py folds this fingerprint into
# the cache key so identical query bytes against different engines or
# policies can never cross-hit)
_ENGINE_SEQ = itertools.count()


@dataclass
class TunedArm:
    """One bucket's autotune verdict: the measured-fastest registered arm
    next to what the static (analytic) selector would have run.

    ``path=None`` / ``bn=None`` mean "registry default" — the winner may
    legitimately BE the static choice, in which case routing through the
    tuned arm is a no-op by construction."""

    strategy: str
    path: Optional[str]
    bn: Optional[int]
    us: float                 # winning measured us per launch
    static_strategy: str
    static_path: str
    static_us: float
    # every (strategy, path, bn, us) measured, for reports and tests
    candidates: List[Tuple] = field(default_factory=list)

    @property
    def differs(self) -> bool:
        """Did measurement overturn the static selector?"""
        return (self.strategy != self.static_strategy
                or (self.path is not None and self.path != self.static_path)
                or self.bn is not None)


class NonNeuralServeEngine:
    """Power-of-two bucket batching over any registered estimator.

    The estimator's ``predict_batch_fn()`` is jitted ONCE with the fitted
    params flowing in as jit arguments (one shared device buffer) — a
    closure would bake a copy of the training set / forest into every
    per-bucket executable.  ``bucket_launches`` counts launches per bucket
    size for capacity accounting.

    Sharded serving (DESIGN.md §5, §9): with ``mesh=`` (or ``sharded=True``
    after a ``fit_sharded`` estimator) each bucket routes to one of three
    partition strategies — ``"reference"`` (model axis sharded, per-shard
    fused kernels + merge collective), ``"query"`` (batch rows sharded
    against a replicated model, zero merge collective), or ``"single"``
    (one device) — all bit-equal to the single-device path.  ``strategy=``
    pins one for every bucket; the default ``"auto"`` asks
    ``dispatch.resolve_strategy`` (core/precision.py's Eq. 15 cost model)
    per (algorithm, bucket, mesh) cell; ``bucket_strategies`` records the
    routing.  Buckets are clamped to at least the shard count and rounded
    to a multiple of it so every shard owns whole query rows.
    """

    def __init__(self, estimator: Estimator, *, max_batch: int = 1024,
                 sharded: bool = False, mesh=None, mesh_axis: str = "data",
                 policy: Optional[str] = None,
                 strategy: Optional[str] = None, max_group: int = 64):
        assert estimator.fitted, "fit the estimator before serving it"
        wants_int8 = (policy is not None
                      and str(policy).split("@")[0] == "int8") \
            or getattr(estimator, "quantized", False)
        if strategy is not None and strategy != "auto" \
                and strategy not in dispatch.STRATEGY_NAMES:
            raise ValueError(f"strategy={strategy!r} is not one of "
                             f"{('auto',) + dispatch.STRATEGY_NAMES}")
        if wants_int8 and (mesh is not None or sharded) \
                and strategy == "reference":
            # the int8 lattices derive from the model-side operand, which a
            # model partition would chunk (DESIGN.md §8/§9) — query keeps the
            # model whole on every shard and stays exact
            raise NotImplementedError(
                "the int8 tier has no model-partition serving arm: use "
                "strategy='query'/'single'/'auto' (auto never routes "
                "quantized params to 'reference')")
        if policy is not None and str(policy).split("@")[0] == "int8":
            # the int8 serving tier: quantize into an ENGINE-LOCAL copy —
            # ``estimator.quantize()`` here would rewrite the CALLER'S
            # params in place, and a second engine (or a ModelStore
            # handle) sharing the estimator would then silently serve
            # int8 under a fp32 policy.  A fit under the int8
            # PrecisionPolicy arrives already quantized and passes
            # through.  The footprint A/B goes through serving/quant.py's
            # byte accounting either way.
            from repro.serving import quant as _q
            if estimator.quantized:
                fp32 = estimator.dequantize_params()
            else:
                fp32 = estimator.params
                estimator = estimator.quantized_copy()
            self.quant_report = {
                "bytes_int8": _q.param_bytes(estimator.params),
                "bytes_fp32": _q.param_bytes(fp32),
                # what quantize_params(min_size=1) WOULD serialize — the
                # shared _should_quantize predicate keeps the estimate and
                # the actual int8 payload accounting in one place
                "bytes_predicted": _q.quant_bytes(fp32, min_size=1),
            }
        else:
            self.quant_report = None
        self.estimator = estimator
        self.algorithm = estimator.algorithm
        self.max_batch = int(max_batch)
        self.bucket_launches: Dict[int, int] = {}
        self.warmed: set = set()   # bucket sizes with a compiled executable
        if mesh is None and sharded:
            mesh = estimator.mesh
            mesh_axis = estimator.mesh_axis
            assert mesh is not None, \
                "sharded=True needs a fit_sharded estimator or mesh="
        self.mesh, self.mesh_axis = mesh, mesh_axis
        self.n_shards = mesh.shape[mesh_axis] if mesh is not None else 1
        self.strategy = strategy           # None/"auto" => cost-model routes
        self._quantized = bool(wants_int8)
        self._cost_shape = estimator.serve_cost_shape()
        self.bucket_strategies: Dict[int, str] = {}
        self.tuned: Dict[int, TunedArm] = {}   # bucket -> autotune verdict
        self._fns: Dict[Tuple, object] = {}    # (strategy, path, bn) -> jit
        self._placed: Dict[str, object] = {}   # strategy -> placed params
        # grouped (multi-tenant) launch state — DESIGN.md §11
        self.max_group = int(max_group)
        self.warmed_groups: Set[Tuple[int, int]] = set()   # (g, b) compiled
        self.group_launches: Dict[Tuple[int, int], int] = {}
        self._gfn = None
        # folded into scheduler result-cache keys: two engines over the
        # SAME estimator (e.g. fp32 and int8 policies) must never cross-hit
        self.cache_fingerprint = (self.algorithm, str(policy),
                                  next(_ENGINE_SEQ))

    @property
    def sharded(self) -> bool:
        return self.mesh is not None

    def sibling(self, *, policy: Optional[str] = None, estimator=None,
                max_batch: Optional[int] = None) -> "NonNeuralServeEngine":
        """An engine over a cheaper representation of the SAME fitted
        model — the brownout-ladder constructor (serving/degrade.py).
        ``policy="int8"`` serves the estimator's ``quantized_copy``;
        ``estimator=`` substitutes an alternate arm (e.g. an ANN index
        over an exact kNN's reference set).  Siblings share this
        engine's bucket geometry unless ``max_batch`` widens it (a
        cheaper tier may absorb a larger per-drain budget).  Single-
        device only: a degraded tier must never be the first thing to
        touch a mesh mid-overload."""
        if self.mesh is not None:
            raise NotImplementedError(
                "brownout siblings are single-device — shard the primary "
                "engine, degrade locally")
        est = self.estimator if estimator is None else estimator
        return NonNeuralServeEngine(
            est, max_batch=int(max_batch or self.max_batch),
            policy=policy, max_group=self.max_group)

    def _bucket(self, b: int) -> int:
        size = 1
        while size < b:
            size *= 2
        size = max(min(size, self.max_batch), self.n_shards)
        # whole query rows per shard: a query partition splits axis 0, so
        # every bucket is a shard-count multiple (no-op on pow2 meshes,
        # where every clamped pow2 bucket already divides)
        return size + (-size) % self.n_shards

    def _route(self, bucket: int) -> str:
        """The partition strategy serving this bucket (cached per bucket)."""
        s = self.bucket_strategies.get(bucket)
        if s is None:
            if self.mesh is None:
                s = "single"
            else:
                s = dispatch.resolve_strategy(
                    self.algorithm, bucket=bucket, n_shards=self.n_shards,
                    strategy=self.strategy, policy=self.estimator.policy,
                    shape=self._cost_shape,
                    quantized=True if self._quantized else None)
            self.bucket_strategies[bucket] = s
        return s

    def _fn_for(self, strategy: str, path: Optional[str] = None,
                bn: Optional[int] = None):
        """The jitted executor for one (strategy, path, bn) arm.
        ``path``/``bn`` override the estimator's own settings through a
        shallow copy (the autotuner's knobs); None keeps them."""
        key = (strategy, path, bn)
        fn = self._fns.get(key)
        if fn is None:
            est = self.estimator
            if path is not None or bn is not None:
                est = _copy.copy(est)
                if path is not None:
                    est.path = path
                if bn is not None:
                    est.bn = bn
            if self.mesh is None or strategy == "single":
                fn = jax.jit(est.predict_batch_fn())
            else:
                fn = jax.jit(est.predict_batch_sharded_fn(
                    self.mesh, self.mesh_axis, strategy))
            self._fns[key] = fn
        return fn

    def _params_for(self, strategy: str):
        """Params placed for the strategy — replicated for query/single
        (PULP-NN's weights-in-every-local-memory layout), row-sharded and
        ``_FAR``-pre-padded for the kNN reference partition so the hot path
        never re-pads (the padding satellite of DESIGN.md §9).  The
        estimator's own params are never mutated."""
        placed = self._placed.get(strategy)
        if placed is None:
            placed = params = self.estimator.params
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                if strategy == "reference" and self.algorithm == "knn" \
                        and not self._quantized:
                    c = self.n_shards
                    A, labels = params.A, params.labels
                    pad = (-A.shape[0]) % c
                    if pad:
                        A = jnp.concatenate(
                            [A, jnp.full((pad, A.shape[1]), _cluster._FAR,
                                         A.dtype)])
                        labels = jnp.concatenate(
                            [labels, jnp.zeros((pad,), labels.dtype)])
                    A = jax.device_put(
                        A, NamedSharding(self.mesh, P(self.mesh_axis)))
                    labels = jax.device_put(
                        labels, NamedSharding(self.mesh, P()))
                    placed = params._replace(A=A, labels=labels)
                else:
                    rep = NamedSharding(self.mesh, P())
                    placed = jax.tree.map(
                        lambda x: jax.device_put(x, rep)
                        if hasattr(x, "shape") else x, params)
            self._placed[strategy] = placed
        return placed

    def _empty(self) -> ClassifyResult:
        return ClassifyResult(classes=jnp.zeros((0,), jnp.int32),
                              aux=self.estimator.empty_aux(), launches=0,
                              algorithm=self.algorithm)

    def _choice(self, bucket: int) -> Tuple[str, Optional[str],
                                            Optional[int]]:
        """The (strategy, path, bn) arm serving this bucket: the autotuned
        winner when ``warmup(autotune=True)`` measured one, else the static
        route with registry-default path."""
        arm = self.tuned.get(bucket)
        if arm is not None:
            return arm.strategy, arm.path, arm.bn
        return self._route(bucket), None, None

    # overridable seam: tests inject scripted timings to flip decisions
    # deterministically, and the benchmark sweeps reuse the same probe
    def _measure(self, fn, params, chunk, iters: int = 3) -> float:
        """Min warm wall-clock (us) of one launch (first call compiles)."""
        jax.block_until_ready(fn(params, chunk)[0])
        best = float("inf")
        for _ in range(iters):
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(params, chunk)[0])
            best = min(best, _time.perf_counter() - t0)
        return best * 1e6

    def _static_arm(self, bucket: int) -> Tuple[str, str]:
        """(strategy, path) the static selectors would run at this bucket."""
        strategy = self._route(bucket)
        op = dispatch.HOT_OPS.get(self.algorithm)
        if self._quantized:
            return strategy, "quant"
        if op is None:
            return strategy, self.estimator.path or "ref"
        kw = dispatch.hot_shape_kw(self.algorithm, self._cost_shape, bucket)
        return strategy, dispatch.resolve(
            self.algorithm, op, path=self.estimator.path,
            policy=self.estimator.policy, **kw).name

    def _autotune_candidates(self, bucket: int):
        """Registered (strategy, path, bn) arms worth timing at this
        bucket.  Never the lossy "quant" arm; explicit ``path=`` /
        ``REPRO_BACKEND`` / ``strategy=`` pins keep precedence by
        collapsing their axis to the pinned value; every candidate comes
        from the dispatch registries so ``bucket_launches ⊆ warmed``
        holds for whatever wins."""
        algo, op = self.algorithm, dispatch.HOT_OPS.get(self.algorithm)
        # --- path axis
        paths: List[Optional[str]] = [None]
        if (op is not None and self.estimator.path is None
                and not self._quantized
                and dispatch.env_override() is None):
            regd = dispatch.registered().get((algo, op), ())
            paths = [p for p in regd if p != "quant"] or [None]
        # --- strategy axis
        if self.mesh is None:
            strategies = ["single"]
        elif self.strategy is not None and self.strategy != "auto":
            strategies = [self.strategy]
        elif dispatch.strategy_env_override() is not None:
            strategies = [dispatch.strategy_env_override()]
        else:
            cands = {st for (a, _, st) in dispatch.sharded_registered()
                     if a == algo}
            if self._quantized:
                cands.discard("reference")
            strategies = ["single"] + sorted(cands)
        # --- bn axis: fused-kernel row blocking (kNN / K-Means only)
        bn_paths = {"fused"}
        arms = [(self._route(bucket), None, None)]   # the static arm
        for s in strategies:
            for p in paths:
                # sharded strategies keep the per-shard registry default:
                # the path axis is a single-device knob (per-shard shapes
                # re-select anyway) and the cross product would explode
                # warmup compile time
                if s != "single" and p is not None:
                    continue
                arms.append((s, p, None))
                if algo in ("knn", "kmeans") and p in bn_paths:
                    for bn in (64, 256):
                        arms.append((s, p, bn))
        seen, uniq = set(), []
        for arm in arms:
            if arm not in seen:
                seen.add(arm)
                uniq.append(arm)
        return uniq

    def _has_executor(self, strategy: str) -> bool:
        """Whether ``strategy`` can be built here: one device always, a
        mesh strategy only where the algorithm registers a sharded arm
        for it (ANN has no "reference" partition).  The autotuner skips
        exactly these arms; any other failure — a kernel the compiler
        refuses — raises."""
        if self.mesh is None or strategy == "single":
            return True
        op = dispatch.HOT_OPS.get(self.algorithm)
        return (self.algorithm, op, strategy) in \
            dispatch.sharded_registered()

    def compiled_text(self, bucket: int, d: int,
                      dtype=jnp.float32) -> str:
        """Compiled HLO of the executor serving ``bucket`` for (bucket, d)
        queries — where a deployment check looks for the Pallas kernels
        (``tpu_custom_call``) and collectives it expects."""
        s, p, bn = self._choice(bucket)
        return self._fn_for(s, p, bn).lower(
            self._params_for(s), jax.ShapeDtypeStruct((bucket, d), dtype)
        ).compile().as_text()

    def _autotune_bucket(self, size: int, chunk) -> TunedArm:
        """Micro-time every registered arm for one bucket, record the
        winner in ``self.tuned``, and route this bucket through it."""
        static_strategy, static_path = self._static_arm(size)
        measured, static_us = [], None
        for s, p, bn in self._autotune_candidates(size):
            if not self._has_executor(s):
                continue
            us = self._measure(self._fn_for(s, p, bn),
                               self._params_for(s), chunk)
            measured.append((s, p, bn, us))
            if (s == static_strategy and bn is None
                    and (p is None or p == static_path)):
                static_us = us if static_us is None else min(static_us, us)
        if not measured:          # nothing ran: keep the static route
            return None
        s, p, bn, us = min(measured, key=lambda m: m[3])
        arm = TunedArm(strategy=s, path=p, bn=bn, us=us,
                       static_strategy=static_strategy,
                       static_path=static_path,
                       static_us=static_us if static_us is not None else us,
                       candidates=measured)
        self.tuned[size] = arm
        self.bucket_strategies[size] = s
        return arm

    def _warm_one(self, size: int, chunk, autotune: bool = False) -> None:
        """Compile one bucket through the jitted fn DIRECTLY — warmup must
        never land in ``bucket_launches``, which counts production launches
        for capacity accounting."""
        pad = size - chunk.shape[0]
        if pad:
            chunk = jnp.pad(chunk, ((0, pad), (0, 0)))
        if autotune:
            if self._autotune_bucket(size, chunk) is not None:
                self.warmed.add(size)
                return
        s, p, bn = self._choice(size)
        jax.block_until_ready(
            self._fn_for(s, p, bn)(self._params_for(s), chunk)[0])
        self.warmed.add(size)

    def warmup(self, X, *, autotune: bool = False) -> int:
        """Compile every bucket a classify(X) call would hit (including the
        smaller trailing-chunk bucket) so jit compiles never land inside a
        caller's timed window.  Returns the number of buckets warmed.
        Compile-time launches do NOT count into ``bucket_launches``.

        ``autotune=True`` additionally micro-times every registered arm
        (paths, block sizes, partition strategies) per bucket and routes
        production launches through the measured winner (``self.tuned``) —
        the paper's profile-then-optimize loop (§5.2) at warmup time.
        Explicit ``path=``/``REPRO_BACKEND``/``strategy=`` pins keep
        precedence."""
        X = jnp.asarray(X)
        sizes = {self._bucket(min(self.max_batch, X.shape[0] - lo))
                 for lo in range(0, X.shape[0], self.max_batch)}
        for size in sorted(sizes):
            self._warm_one(size, X[:size], autotune=autotune)
        return len(sizes)

    def warmup_buckets(self, d: int, *, dtype=jnp.float32,
                       autotune: bool = False) -> int:
        """Compile EVERY bucket ``classify`` can ever route a (B, d) batch
        to — what a request-stream scheduler needs so no jit compile can
        land mid-stream (scheduler.py coalesces only into ``warmed``).
        Returns the number of buckets warmed.  ``autotune=True`` as in
        ``warmup``."""
        sizes, b = set(), 1
        while b < 2 * self.max_batch:
            sizes.add(self._bucket(b))
            b *= 2
        for size in sorted(sizes):
            self._warm_one(size, jnp.zeros((size, d), dtype),
                           autotune=autotune)
        return len(sizes)

    def classify(self, X) -> ClassifyResult:
        """X: (B, d) queries -> per-query prediction + aux evidence."""
        X = jnp.asarray(X)
        B = X.shape[0]
        if B == 0:
            return self._empty()
        classes, auxes, launches = [], [], 0
        for lo in range(0, B, self.max_batch):
            chunk = X[lo: lo + self.max_batch]
            bucket = self._bucket(chunk.shape[0])
            pad = bucket - chunk.shape[0]
            if pad:
                chunk = jnp.pad(chunk, ((0, pad), (0, 0)))
            s, p, bn = self._choice(bucket)
            cls, aux = self._fn_for(s, p, bn)(self._params_for(s), chunk)
            classes.append(cls[: bucket - pad])
            auxes.append(aux[: bucket - pad])
            self.bucket_launches[bucket] = \
                self.bucket_launches.get(bucket, 0) + 1
            self.warmed.add(bucket)
            launches += 1
        return ClassifyResult(classes=jnp.concatenate(classes),
                              aux=jnp.concatenate(auxes),
                              launches=launches,
                              algorithm=self.algorithm)

    # ------------------------------------------------ grouped (multi-tenant)

    def _group_bucket(self, g: int) -> int:
        """Power-of-two model-group bucket covering ``g`` tenants, so at
        most log2(max_group) x log2(max_batch) grouped executables exist."""
        size = 1
        while size < g:
            size *= 2
        return size

    def group_fn(self):
        """The jitted grouped launch: the estimator's ``predict_batch_fn``
        vmapped over the model-group axis (``dispatch.grouped``), jitted
        ONCE — stacked params flow in as jit arguments (shared device
        buffers), and each (group-bucket, bucket) shape gets its own
        executable under the same callable."""
        if self._gfn is None:
            if self.mesh is not None:
                raise NotImplementedError(
                    "grouped (multi-tenant) serving is single-device: the "
                    "vmapped model-group axis and a mesh partition are "
                    "separate batching dimensions — drop mesh=")
            self._gfn = jax.jit(self.estimator.predict_batch_group_fn())
        return self._gfn

    @staticmethod
    def _group_resize(stacked, g: int):
        """Slice or pad (repeating the last model row) a stacked params
        pytree to exactly ``g`` lanes — padding lanes compute throwaway
        predictions that are sliced off."""
        def one(leaf):
            if not hasattr(leaf, "shape"):
                return leaf
            have = leaf.shape[0]
            if have == g:
                return leaf
            if have > g:
                return leaf[:g]
            return jnp.concatenate(
                [leaf, jnp.repeat(leaf[-1:], g - have, axis=0)])

        return jax.tree.map(one, stacked)

    def classify_group(self, stacked_params, Xg) -> GroupClassifyResult:
        """One multi-tenant launch: stacked params (G, ...) + queries
        (G, B, d) -> per-tenant (G, B) predictions, bit-equal per lane to
        ``classify`` with that tenant's params.  G pads to the
        power-of-two group bucket (repeating the last model), B pads to
        the query bucket; B beyond ``max_batch`` microbatches along the
        query axis."""
        Xg = jnp.asarray(Xg)
        assert Xg.ndim == 3, f"Xg must be (G, B, d), got {Xg.shape}"
        G, B = Xg.shape[0], Xg.shape[1]
        gb = self._group_bucket(G)
        if G > self._group_bucket(self.max_group):
            raise ValueError(
                f"{G} models exceed max_group={self.max_group} — split the "
                f"group (the scheduler's drain does this automatically)")
        if gb > G:
            Xg = jnp.concatenate(
                [Xg, jnp.zeros((gb - G,) + Xg.shape[1:], Xg.dtype)])
        stacked = self._group_resize(stacked_params, gb)
        fn = self.group_fn()
        classes, auxes, launches = [], [], 0
        for lo in range(0, B, self.max_batch):
            chunk = Xg[:, lo: lo + self.max_batch] if B > self.max_batch \
                else Xg
            bucket = self._bucket(chunk.shape[1])
            pad = bucket - chunk.shape[1]
            if pad:
                chunk = jnp.pad(chunk, ((0, 0), (0, pad), (0, 0)))
            cls, aux = fn(stacked, chunk)
            if pad:     # no-op slices still dispatch eagerly — skip them
                cls, aux = cls[:, : bucket - pad], aux[:, : bucket - pad]
            classes.append(cls)
            auxes.append(aux)
            self.group_launches[(gb, bucket)] = \
                self.group_launches.get((gb, bucket), 0) + 1
            self.warmed_groups.add((gb, bucket))
            launches += 1
        cls = classes[0] if launches == 1 \
            else jnp.concatenate(classes, axis=1)
        aux = auxes[0] if launches == 1 else jnp.concatenate(auxes, axis=1)
        if gb > G:
            cls, aux = cls[:G], aux[:G]
        return GroupClassifyResult(classes=cls, aux=aux,
                                   launches=launches,
                                   algorithm=self.algorithm)

    def warmup_groups(self, stacked_params, d: int, *, g_sizes=None,
                      b_sizes=None, dtype=jnp.float32) -> int:
        """Compile every (group-bucket, bucket) cell a tenant stream can
        route to — the grouped analogue of ``warmup_buckets`` (the
        scheduler coalesces only into ``warmed_groups``, so no jit
        compile lands mid-stream).  ``g_sizes``/``b_sizes`` restrict the
        lattice (benchmarks warm exactly the cells they time).  Warmup
        never lands in ``group_launches``.  Returns cells compiled."""
        fn = self.group_fn()
        if g_sizes is None:
            gs, g = set(), 1
            top = self._group_bucket(self.max_group)
            while g <= top:
                gs.add(g)
                g *= 2
        else:
            gs = {self._group_bucket(g) for g in g_sizes}
        if b_sizes is None:
            bs, b = set(), 1
            while b < 2 * self.max_batch:
                bs.add(self._bucket(b))
                b *= 2
        else:
            bs = {self._bucket(b) for b in b_sizes}
        n = 0
        for g in sorted(gs):
            stacked = self._group_resize(stacked_params, g)
            for b in sorted(bs):
                jax.block_until_ready(
                    fn(stacked, jnp.zeros((g, b, d), dtype))[0])
                self.warmed_groups.add((g, b))
                n += 1
        return n


class KNNServeEngine(NonNeuralServeEngine):
    """Batched kNN classification (the original Non-Neural serving facade,
    now one ``NonNeuralServeEngine`` instantiation away from the other four
    pipelines)."""

    def __init__(self, model: _knn.KNNModel, k: int, *,
                 max_batch: int = 1024):
        assert 1 <= k <= model.A.shape[0], (k, model.A.shape)
        self.model = model
        self.k = int(k)
        super().__init__(KNNEstimator.from_params(model, k=k),
                         max_batch=max_batch)


@dataclass
class GenerationResult:
    tokens: jnp.ndarray        # (B, n_new)
    logprobs: jnp.ndarray      # (B, n_new)
    steps: int


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, serve_cfg: ServeConfig = None):
        self.cfg = cfg
        self.params = params
        self.serve_cfg = serve_cfg or ServeConfig()
        self._prefill = jax.jit(
            functools.partial(transformer.prefill, cfg=cfg,
                              max_seq=self.serve_cfg.max_seq),
            static_argnames=())
        self._decode = jax.jit(
            lambda p, c, t: transformer.decode_step(p, c, t, cfg))

    def prefill(self, tokens, **frontend):
        """tokens: (B, S) -> (last logits, cache)."""
        return self._prefill(self.params, tokens, **frontend)

    def generate(self, prompt_tokens, n_new: int, *, temperature: float = 0.0,
                 key: Optional[jax.Array] = None, **frontend
                 ) -> GenerationResult:
        if temperature > 0.0 and key is None:
            # validate BEFORE prefill: without this the first sampling step
            # dies inside jax.random.split(None) with an opaque traceback
            raise ValueError(
                "generate(temperature>0) samples and needs key= (a jax "
                "PRNGKey for reproducible draws); greedy decoding "
                "(temperature=0.0) needs no key")
        logits, cache = self.prefill(prompt_tokens, **frontend)
        B = prompt_tokens.shape[0]
        toks, lps = [], []
        for i in range(n_new):
            if temperature > 0.0:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, logits / temperature, axis=-1)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            lp = jax.nn.log_softmax(logits, axis=-1)[jnp.arange(B), nxt]
            toks.append(nxt)
            lps.append(lp)
            logits, cache = self._decode(self.params, cache, nxt[:, None])
        return GenerationResult(tokens=jnp.stack(toks, axis=1),
                                logprobs=jnp.stack(lps, axis=1),
                                steps=n_new)
