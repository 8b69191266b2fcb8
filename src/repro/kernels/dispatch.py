"""Kernel dispatch layer: one registry for every Non-Neural hot-path op.

The paper's core claim is "one parallel library serves all Non-Neural
kernels across three FP backends" (§3.4).  This module is that library's
TPU-side spine: a registry keyed by ``(algorithm, op)`` where each op owns
up to three executable paths

  ``fused``   — streaming Pallas kernel (VMEM-resident accumulator,
                DESIGN.md §3),
  ``blocked`` — blocked Pallas kernel composition (tiles round-trip HBM),
  ``ref``     — the pure-jnp oracle from ``kernels/ref.py`` (interpret
                fallback; also the arm for ops whose work is
                integer/gather-bound and gains nothing from a Pallas
                kernel — see DESIGN.md §4),
  ``quant``   — the int8 lattice arm (kernels/quantized.py): per-feature
                symmetric scales derived from the op's reference-side
                operand, exact integer distance/score arithmetic — the
                repo's analogue of the paper's FP-representation rungs
                (DESIGN.md §8).  Lossy by design, so the shape selector
                never picks it: only an explicit ``path="quant"`` /
                ``REPRO_BACKEND=quant`` or a quantized estimator does,

selected per shape against the VMEM budget.  ``REPRO_BACKEND`` (env) or an
explicit ``path=`` kwarg overrides the selector; explicit ``path=`` wins
over the environment.  Every op MUST register a ``ref`` arm so
``REPRO_BACKEND=ref`` can force the whole suite onto the oracle paths (the
second CI matrix entry), and every batched classify op registers a
``quant`` arm so ``REPRO_BACKEND=quant`` forces the int8 tier suite-wide
(the third matrix entry).

``PrecisionPolicy`` threads the paper's three-FP-backend axis (§3.4,
Figs. 9–11) through every layer: a compute dtype (fp32 native vs bf16
reduced precision) plus an analytic cost backend — the libgcc / rvfplib /
fpu cycles-per-op vectors from ``core.precision.BACKENDS`` — so serving
and benchmarks can report both measured wall-clock and modelled
soft-float/FPU cycle costs for the same call.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax.numpy as jnp

from repro.kernels import ops, ref


def _precision_mod():
    # deferred: repro.core's package __init__ imports the algorithm modules,
    # which import this module — a top-level import here would cycle
    from repro.core import precision
    return precision

ENV_VAR = "REPRO_BACKEND"
# "quant" is listed after "ref" so ops without a selector still default to
# the exact arms (resolve() falls back to the first registered name here)
PATH_NAMES = ("fused", "blocked", "ref", "quant")
VMEM_BUDGET = ops._VMEM_BUDGET

# re-exported: the working-set formula IS the dispatch criterion, so the
# benchmark block-model (benchmarks/kernel_blocks.py) imports it from here
fused_topk_working_set_bytes = ops.fused_topk_working_set_bytes

# algorithm -> census key in core.precision.PAPER_CENSUSES ("ann" maps to
# the paper's kNN census: the probe+ADC structure has no paper analogue,
# and serve-side costing uses precision.serve_census("ann") instead)
_CENSUS_KEY = {"knn": "knn", "kmeans": "kmeans_iter", "gnb": "gnb",
               "gmm": "gmm_iter", "rf": "rf", "lr": "lr", "svm": "svm",
               "ann": "knn"}

# algorithm -> its serve-time hot op in the registry: the one op the
# autotuner times and the sweeps record (the estimator's predict_batch hot
# loop is exactly one dispatch through this op)
HOT_OPS = {"knn": "distance_topk", "kmeans": "distance_argmin",
           "gnb": "scores", "gmm": "responsibilities",
           "rf": "forest_votes", "ann": "adc_topk"}


def hot_shape_kw(algorithm: str, cost_shape: Dict[str, int],
                 bucket: int) -> Dict[str, int]:
    """Translate an estimator's ``serve_cost_shape()`` dict plus a batch
    bucket into the shape kwargs ``resolve`` expects for its hot op — one
    shared mapping so the engine autotuner and the benchmark sweeps name
    shapes identically."""
    s = dict(cost_shape or {})
    if algorithm == "knn":
        return {"N": s.get("N", 0), "d": s.get("d", 0), "Q": bucket,
                "k": s.get("k", 1)}
    if algorithm == "kmeans":
        return {"N": bucket, "d": s.get("d", 0), "K": s.get("K", 1)}
    if algorithm == "gnb":
        return {"B": bucket, "d": s.get("d", 0), "C": s.get("C", 1)}
    if algorithm == "gmm":
        return {"B": bucket, "d": s.get("d", 0), "k": s.get("K", 1)}
    if algorithm == "ann":
        return {"Q": bucket, "L": s.get("L", 0), "m": s.get("m", 1),
                "n_codes": s.get("n_codes", 256), "k": s.get("k", 1)}
    return {}    # rf: the forest-vote op resolves shape-free


def _bucket_hint(shape_kw: Dict[str, int]) -> Optional[int]:
    """Batch-size hint from resolve()'s shape kwargs: the query-count axis
    under each op's naming (kNN/ANN ``Q``, GNB/GMM ``B``, K-Means ``N``)."""
    for key in ("Q", "B", "N"):
        if key in shape_kw:
            return int(shape_kw[key])
    return None


# ---------------------------------------------------------------------------
# PrecisionPolicy — the §3.4 backend axis as a value threaded through layers
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PrecisionPolicy:
    """Compute dtype + analytic cost backend.

    ``dtype`` is what estimators cast float inputs/params to (fp32 = the
    paper's FPU-native arm, bf16 = the reduced-precision arm the MXU
    natively supports).  ``cost_backend`` names a cycles-per-op vector in
    ``core.precision.BACKENDS`` used for the analytic soft-float-emulation
    costing (the TPU has no FP-emulation mode to measure, DESIGN.md §6).
    """

    name: str
    dtype: Any
    cost_backend: str = "fpu"

    @property
    def quantized(self) -> bool:
        """True for the int8 tier: inputs stay fp32 at the API boundary
        (quantization is an explicit lattice step, not a dtype cast) and
        estimators rewrite their fitted params to int8 at the end of
        ``fit`` (core/quantization.py)."""
        return self.name.split("@")[0] == "int8"

    def cast(self, x):
        """Cast float arrays to the policy dtype; integers pass through."""
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(self.dtype)
        return x

    def with_cost_backend(self, backend: str) -> "PrecisionPolicy":
        assert backend in _precision_mod().BACKENDS, backend
        return replace(self, cost_backend=backend,
                       name=f"{self.name.split('@')[0]}@{backend}")

    def estimated_cycles(self, algorithm: str,
                         section: str = "total") -> float:
        """Analytic per-inference cycle cost of ``algorithm`` under this
        policy's cost backend (census x cycles-per-op, paper Eq. in §5.2)."""
        precision = _precision_mod()
        key = _CENSUS_KEY.get(algorithm)
        if key is None or key not in precision.PAPER_CENSUSES:
            raise ValueError(
                f"no census for algorithm {algorithm!r} — known: "
                f"{sorted(_CENSUS_KEY)}; add a census_* entry to "
                "core/precision.py and map it in dispatch._CENSUS_KEY "
                "before costing it")
        census = precision.PAPER_CENSUSES[key]
        backend = precision.BACKENDS[self.cost_backend]
        return precision.predicted_cycles(census, backend, section)


POLICIES: Dict[str, PrecisionPolicy] = {
    "fp32": PrecisionPolicy("fp32", jnp.float32, "fpu"),
    "bf16": PrecisionPolicy("bf16", jnp.bfloat16, "fpu"),
    # int8: float inputs pass through (the lattice quantization happens in
    # the quant arms / quantized estimators, not as a cast); costed with
    # the int8 SIMD backend (PULP-NN style 4x MACs, core/precision.py)
    "int8": PrecisionPolicy("int8", jnp.float32, "int8"),
}
DEFAULT_POLICY = POLICIES["fp32"]


def get_policy(name: str) -> PrecisionPolicy:
    """``"fp32"``, ``"bf16"``, or ``"<dtype>@<cost_backend>"``."""
    base, _, backend = name.partition("@")
    policy = POLICIES[base]
    return policy.with_cost_backend(backend) if backend else policy


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


class KernelPath(NamedTuple):
    algorithm: str
    op: str
    name: str          # "fused" | "blocked" | "ref"
    fn: Callable


_PATHS: Dict[Tuple[str, str], Dict[str, Callable]] = {}
_SELECTORS: Dict[Tuple[str, str], Callable[..., str]] = {}


def register(algorithm: str, op: str, path: str):
    assert path in PATH_NAMES, path

    def deco(fn):
        _PATHS.setdefault((algorithm, op), {})[path] = fn
        return fn

    return deco


def selector(algorithm: str, op: str):
    def deco(fn):
        _SELECTORS[(algorithm, op)] = fn
        return fn

    return deco


def registered() -> Dict[Tuple[str, str], Tuple[str, ...]]:
    """(algorithm, op) -> available path names, for docs and tests."""
    return {k: tuple(n for n in PATH_NAMES if n in v)
            for k, v in sorted(_PATHS.items())}


def env_override() -> Optional[str]:
    v = os.environ.get(ENV_VAR, "").strip()
    if not v:
        return None
    if v not in PATH_NAMES:
        # a typo'd REPRO_BACKEND must not silently run the default arms —
        # the ref CI matrix entry would report green without testing ref
        raise ValueError(f"{ENV_VAR}={v!r} is not one of {PATH_NAMES}")
    return v


# ---------------------------------------------------------------------------
# Active cost model — analytic by default, calibrated when installed
# ---------------------------------------------------------------------------
#
# One process-wide CostModel (core/precision.py) that both the path
# selectors (resolve) and the strategy selector (resolve_strategy)
# consult.  ``REPRO_CALIBRATION=<path to CALIBRATION.json>`` installs a
# calibrated model at first use; ``set_cost_model`` installs one
# programmatically (serve.py --calibration, tests).  The analytic model
# is inert in ``resolve`` — ``preferred_path`` returns None without
# measured rows — so uncalibrated behaviour is bit-identical to the
# historical shape/VMEM selectors.

CALIBRATION_ENV_VAR = "REPRO_CALIBRATION"
_COST_MODEL = None
_ENV_CALIBRATION_LOADED = False


def set_cost_model(model) -> None:
    """Install (or with None, clear) the process-wide CostModel."""
    global _COST_MODEL, _ENV_CALIBRATION_LOADED
    _COST_MODEL = model
    _ENV_CALIBRATION_LOADED = model is not None


def active_cost_model():
    """The installed CostModel, loading ``REPRO_CALIBRATION`` once if set;
    falls back to the shared analytic model."""
    global _COST_MODEL, _ENV_CALIBRATION_LOADED
    if _COST_MODEL is None and not _ENV_CALIBRATION_LOADED:
        _ENV_CALIBRATION_LOADED = True
        src = os.environ.get(CALIBRATION_ENV_VAR, "").strip()
        if src:
            _COST_MODEL = _precision_mod().CostModel.from_calibration(src)
    if _COST_MODEL is None:
        _COST_MODEL = _precision_mod().CostModel.analytic()
    return _COST_MODEL


def resolve(algorithm: str, op: str, *, path: Optional[str] = None,
            policy: Optional[PrecisionPolicy] = None,
            budget: int = VMEM_BUDGET, cost_model=None,
            **shape_kw) -> KernelPath:
    """Pick the executable path for ``(algorithm, op)`` at these shapes.

    Precedence: explicit ``path=`` > ``REPRO_BACKEND`` env (when that op
    has the requested arm) > a calibrated cost model's measured-fastest
    fp32 path near this batch bucket > the op's shape/VMEM selector.
    The lossy "quant" arm is never picked implicitly, measured or not.
    """
    key = (algorithm, op)
    if key not in _PATHS:
        raise KeyError(f"no kernel registered for {key}; "
                       f"known: {sorted(_PATHS)}")
    paths = _PATHS[key]
    if path is not None:
        if path not in paths:
            raise KeyError(f"{key} has no {path!r} path "
                           f"(has {sorted(paths)})")
        chosen = path
    else:
        env = env_override()
        if env is not None and env in paths:
            chosen = env
        else:
            chosen = None
            cm = cost_model if cost_model is not None else \
                active_cost_model()
            if cm.calibrated and not (policy is not None
                                      and policy.quantized):
                pref = cm.preferred_path(algorithm,
                                         bucket=_bucket_hint(shape_kw))
                if pref in paths and pref != "quant":
                    chosen = pref
            if chosen is None:
                sel = _SELECTORS.get(key)
                if sel is not None:
                    chosen = sel(policy=policy or DEFAULT_POLICY,
                                 budget=budget, **shape_kw)
                else:
                    chosen = next(n for n in PATH_NAMES if n in paths)
    return KernelPath(algorithm, op, chosen, paths[chosen])


# ---------------------------------------------------------------------------
# kNN — fused distance->top-k (Fig. 6 OP1+OP2)
# ---------------------------------------------------------------------------


@register("knn", "distance_topk", "fused")
def _knn_fused(a, c, k, *, bn=None, interpret=None):
    return ops.distance_topk(a, c, k, bn=bn, interpret=interpret)


@register("knn", "distance_topk", "blocked")
def _knn_blocked(a, c, k, *, bn=None, interpret=None):
    # the pre-fusion two-pass composition: (N, Q) e matrix through HBM
    e = ops.pairwise_sq_dist(a, c, interpret=interpret)
    return ops.topk_smallest(jnp.transpose(e), k, interpret=interpret)


@register("knn", "distance_topk", "ref")
def _knn_ref(a, c, k, *, bn=None, interpret=None):
    return ref.distance_topk(a, c, k)


@register("knn", "distance_topk", "quant")
def _knn_quant(a, c, k, *, bn=None, interpret=None):
    """Dynamic int8 arm: per-feature scales derived from the REFERENCE
    rows (never the query batch, so single-query and batched calls share
    one lattice and ``predict == predict_batch`` stays exact); distances
    are exact lattice integers, dequantized with the mean squared scale."""
    from repro.kernels import quantized as qk
    scale = qk.feature_scales(jnp.max(jnp.abs(a.astype(jnp.float32)),
                                      axis=0))
    aq = qk.quantize_rows(a, scale)
    cq = qk.quantize_rows(c, scale)
    vals, idx = qk.distance_topk_q8(aq, cq, k, bn=bn, interpret=interpret)
    return vals.astype(jnp.float32) * jnp.mean(scale * scale), idx


@selector("knn", "distance_topk")
def _knn_select(*, N, d, Q, k, policy=None, budget=VMEM_BUDGET):
    # fused streams A in bn-row blocks but keeps C, the merge window, and
    # the (Q, k) accumulator resident; if even the minimum bn=8 block
    # overflows VMEM (huge Q*d), fall back to the blocked two-pass
    if ops.fused_topk_working_set_bytes(8, d, Q, k) <= budget:
        return "fused"
    return "blocked"


def distance_topk(a, c, k: int, *, policy: Optional[PrecisionPolicy] = None,
                  path: Optional[str] = None, bn: Optional[int] = None,
                  interpret: Optional[bool] = None):
    """A (N, d) data, C (Q, d) queries -> (values (Q, k), indices (Q, k))."""
    if policy is not None:
        a, c = policy.cast(a), policy.cast(c)
    N, d = a.shape
    kp = resolve("knn", "distance_topk", path=path, policy=policy,
                 N=N, d=d, Q=c.shape[0], k=k)
    return kp.fn(a, c, k, bn=bn, interpret=interpret)


# ---------------------------------------------------------------------------
# K-Means — fused distance->argmin (Fig. 7 OP1+OP2, Selection Sort k=1)
# ---------------------------------------------------------------------------


def argmin_working_set_bytes(bn: int, d: int, K: int) -> int:
    """VMEM working set of one fused distance->argmin grid step: the
    double-buffered (bn, d) A tile, resident (K, d) centroids, and the
    (bn, K) distance tile consumed in place."""
    return 2 * bn * d * 4 + K * d * 4 + bn * K * 4 + 2 * bn * 8


@register("kmeans", "distance_argmin", "fused")
def _km_fused(a, c, *, bn=None, interpret=None):
    return ops.distance_argmin(a, c, interpret=interpret) if bn is None \
        else ops.distance_argmin(a, c, bn=bn, interpret=interpret)


@register("kmeans", "distance_argmin", "blocked")
def _km_blocked(a, c, *, bn=None, interpret=None):
    e = ops.pairwise_sq_dist(a, c, interpret=interpret)
    return jnp.min(e, axis=1), jnp.argmin(e, axis=1).astype(jnp.int32)


@register("kmeans", "distance_argmin", "ref")
def _km_ref(a, c, *, bn=None, interpret=None):
    return ref.distance_argmin(a, c)


@register("kmeans", "distance_argmin", "quant")
def _km_quant(a, c, *, bn=None, interpret=None):
    from repro.kernels import quantized as qk
    scale = qk.feature_scales(jnp.max(jnp.abs(c.astype(jnp.float32)),
                                      axis=0))
    aq = qk.quantize_rows(a, scale)
    cq = qk.quantize_rows(c, scale)
    vals, idx = qk.distance_argmin_q8(aq, cq, interpret=interpret) \
        if bn is None else qk.distance_argmin_q8(aq, cq, bn=bn,
                                                 interpret=interpret)
    return vals.astype(jnp.float32) * jnp.mean(scale * scale), idx


@selector("kmeans", "distance_argmin")
def _km_select(*, N, d, K, policy=None, budget=VMEM_BUDGET):
    if argmin_working_set_bytes(8, d, K) <= budget:
        return "fused"
    return "blocked"


def distance_argmin(a, c, *, policy: Optional[PrecisionPolicy] = None,
                    path: Optional[str] = None, bn: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """A (N, d), centroids (K, d) -> (min sq-dist (N,), nearest id (N,))."""
    if policy is not None:
        a, c = policy.cast(a), policy.cast(c)
    N, d = a.shape
    kp = resolve("kmeans", "distance_argmin", path=path, policy=policy,
                 N=N, d=d, K=c.shape[0])
    return kp.fn(a, c, bn=bn, interpret=interpret)


# ---------------------------------------------------------------------------
# GNB — batched joint log-likelihood (Fig. 5 OP1+OP2)
# ---------------------------------------------------------------------------


@register("gnb", "scores", "blocked")
def _gnb_blocked(X, mu, var, log_prior, *, interpret=None):
    return ops.gnb_scores_batch(X, mu, var, log_prior, interpret=interpret)


@register("gnb", "scores", "ref")
def _gnb_ref(X, mu, var, log_prior, *, interpret=None):
    return ref.gnb_scores_batch(X, mu, var, log_prior)


@register("gnb", "scores", "quant")
def _gnb_quant(X, mu, var, log_prior, *, interpret=None):
    """int8 features against precomputed per-class affine score tables:
    the Gaussian divide/log work folds into calibration, the hot loop is
    two (B, d) x (d, C) matmuls over exact integer features."""
    from repro.core import quantization as cq
    from repro.kernels import quantized as qk
    scale = qk.feature_scales(cq.gauss_absmax(mu.astype(jnp.float32),
                                              var.astype(jnp.float32)))
    quad, lin, const = cq.gauss_score_tables(mu, var, scale)
    xq = qk.quantize_rows(X, scale)
    return qk.affine_scores(xq, quad, lin, const + log_prior)


@selector("gnb", "scores")
def _gnb_select(*, B, d, C, policy=None, budget=VMEM_BUDGET):
    # at small d the feature-chunked kernel is all launch overhead; the
    # vertical split only pays once there are several 128-lane chunks
    if d >= 64:
        return "blocked"
    return "ref"


def gnb_scores(X, mu, var, log_prior, *,
               policy: Optional[PrecisionPolicy] = None,
               path: Optional[str] = None,
               interpret: Optional[bool] = None):
    """X (B, d) queries -> (B, C) joint log-likelihood."""
    if policy is not None:
        X, mu, var = policy.cast(X), policy.cast(mu), policy.cast(var)
    B, d = X.shape
    kp = resolve("gnb", "scores", path=path, policy=policy,
                 B=B, d=d, C=mu.shape[0])
    return kp.fn(X, mu, var, log_prior, interpret=interpret)


# ---------------------------------------------------------------------------
# GMM — E-step responsibilities (GNB OP1/OP2 + Fig. 6 row chunking)
# ---------------------------------------------------------------------------


@register("gmm", "responsibilities", "ref")
def _gmm_ref(mu, var, log_pi, X, *, n_cores=8, interpret=None):
    # ref-only by design: the E-step is a (B, k, d) log-density reduction
    # at small k whose accumulation order is load-bearing for EM
    # convergence parity; the chunked-vmap path IS the reference schedule
    from repro.core.gmm import gmm_e_step
    return gmm_e_step(X, mu, var, log_pi, n_cores)


@register("gmm", "responsibilities", "blocked")
def _gmm_blocked(mu, var, log_pi, X, *, n_cores=8, interpret=None):
    """GMM joint log-density IS GNB's per-class score with log_pi as the
    prior, so the blocked feature-chunked Pallas kernel serves both: one
    (B, k) GEMM-shaped score pass, then the per-row logsumexp
    normalisation.  Same (log_resp, mean log-lik) contract as the ref arm
    but a different accumulation order — the d >= 64 selector threshold
    keeps the default small-d EM fits on the ref schedule."""
    import jax

    joint = ops.gnb_scores_batch(X, mu, var, log_pi, interpret=interpret)
    norm = jax.nn.logsumexp(joint, axis=1, keepdims=True)
    return joint - norm, jnp.mean(norm[:, 0])


@register("gmm", "responsibilities", "quant")
def _gmm_quant(mu, var, log_pi, X, *, n_cores=8, interpret=None):
    """GMM E-step over the lattice: the same affine-table GEMM identity as
    GNB, normalized per row.  The mean log-likelihood is computed from the
    quantized joints (same contract as the ref arm)."""
    import jax

    from repro.core import quantization as cq
    from repro.kernels import quantized as qk
    scale = qk.feature_scales(cq.gauss_absmax(mu.astype(jnp.float32),
                                              var.astype(jnp.float32)))
    quad, lin, const = cq.gauss_score_tables(mu, var, scale)
    joint = qk.affine_scores(qk.quantize_rows(X, scale), quad, lin,
                             const + log_pi)
    norm = jax.nn.logsumexp(joint, axis=1, keepdims=True)
    return joint - norm, jnp.mean(norm[:, 0])


@selector("gmm", "responsibilities")
def _gmm_select(*, B=0, d=0, k=0, policy=None, budget=VMEM_BUDGET):
    # mirror the GNB threshold: the feature-chunked kernel only pays once
    # there are several 128-lane chunks; small-d stays on the ref schedule
    # (whose accumulation order is load-bearing for EM convergence parity)
    if d >= 64:
        return "blocked"
    return "ref"


def gmm_responsibilities(mu, var, log_pi, X, *,
                         policy: Optional[PrecisionPolicy] = None,
                         path: Optional[str] = None, n_cores: int = 8,
                         interpret: Optional[bool] = None):
    """X (B, d) -> (log-responsibilities (B, k), mean log-likelihood)."""
    if policy is not None:
        mu, var, X = policy.cast(mu), policy.cast(var), policy.cast(X)
    kp = resolve("gmm", "responsibilities", path=path, policy=policy,
                 B=X.shape[0], d=X.shape[1], k=mu.shape[0])
    return kp.fn(mu, var, log_pi, X, n_cores=n_cores, interpret=interpret)


# ---------------------------------------------------------------------------
# ANN — IVF-PQ asymmetric-distance scoring (DESIGN.md §10)
# ---------------------------------------------------------------------------


@register("ann", "adc_topk", "fused")
def _ann_fused(qlut, codes, cand_ids, k, *, bl=None, interpret=None):
    from repro.kernels import ann as annk
    return annk.adc_topk(qlut, codes, cand_ids, k, bl=bl,
                         interpret=interpret)


@register("ann", "adc_topk", "ref")
def _ann_ref(qlut, codes, cand_ids, k, *, bl=None, interpret=None):
    from repro.kernels import ann as annk
    return annk.ref_adc_topk(qlut, codes, cand_ids, k)


@selector("ann", "adc_topk")
def _ann_select(*, Q, L, m, n_codes, k, policy=None, budget=VMEM_BUDGET):
    # the streaming kernel keeps the (Q, m*n_codes) LUT resident; if even
    # its minimum 128-lane candidate block (queries padded to 32 rows)
    # overflows VMEM (huge Q*m*n_codes), fall back to the dense oracle
    from repro.kernels import ann as annk
    if annk.adc_working_set_bytes(128, max(Q, 32), m, n_codes, k) <= budget:
        return "fused"
    return "ref"


def adc_topk(qlut, codes, cand_ids, k: int, *,
             policy: Optional[PrecisionPolicy] = None,
             path: Optional[str] = None, bl: Optional[int] = None,
             interpret: Optional[bool] = None):
    """Per-query integer LUTs (Q, m*n_codes), candidate PQ codes
    (Q, L, m) int8 + ids (Q, L) -> (ADC distances (Q, k) int32,
    candidate positions (Q, k)).  Integer end to end: no policy cast
    (the int8 policy has no ANN tier — core/ann.py refuses it)."""
    Q, L, m = codes.shape
    kp = resolve("ann", "adc_topk", path=path, policy=policy,
                 Q=Q, L=L, m=m, n_codes=qlut.shape[1] // max(m, 1), k=k)
    return kp.fn(qlut, codes, cand_ids, k, bl=bl, interpret=interpret)


# ---------------------------------------------------------------------------
# RF — batched forest vote (Fig. 8 Independent-Tasks)
# ---------------------------------------------------------------------------


@register("rf", "forest_votes", "ref")
def _rf_ref(feature, threshold, left, right, X, *, n_class, n_cores=8,
            interpret=None):
    # ref-only by design: tree traversal is integer gather + branch work
    # (6.39% FLOP intensity, paper §5.2) — there is no MXU/VPU win to fuse
    from repro.core.random_forest import Forest, forest_classify_batch
    forest = Forest(feature=feature, threshold=threshold, left=left,
                    right=right, n_class=n_class)
    return forest_classify_batch(forest, X, n_cores)


@register("rf", "forest_votes", "quant")
def _rf_quant(feature, threshold, left, right, X, *, n_class, n_cores=8,
              interpret=None):
    """int8 threshold-compare traversal: thresholds and features land on
    the same per-feature lattice (scales from the thresholds — the only
    feature statistics the fitted forest carries), so every node compare
    is int8 vs int8.  The gather/branch structure is unchanged — exactly
    why the paper's RF only gains 2.48x from a better FP backend (§5.2)."""
    from repro.core import quantization as cq
    from repro.core.random_forest import Forest, forest_classify_batch
    from repro.kernels import quantized as qk
    d = X.shape[1]
    forest = Forest(feature=feature, threshold=threshold, left=left,
                    right=right, n_class=n_class)
    qf = cq.quantize_forest(forest, d=d)
    int_forest = Forest(feature=qf.feature, threshold=qf.qthreshold,
                        left=qf.left, right=qf.right, n_class=n_class)
    return forest_classify_batch(int_forest, qk.quantize_rows(X, qf.scale),
                                 n_cores)


def forest_votes(forest, X, *, policy: Optional[PrecisionPolicy] = None,
                 path: Optional[str] = None, n_cores: int = 8,
                 interpret: Optional[bool] = None):
    """Forest params + X (B, d) -> (classes (B,), votes (B, n_class))."""
    if policy is not None:
        X = policy.cast(X)
    kp = resolve("rf", "forest_votes", path=path, policy=policy)
    return kp.fn(forest.feature, forest.threshold, forest.left, forest.right,
                 X, n_class=forest.n_class, n_cores=n_cores,
                 interpret=interpret)


# ---------------------------------------------------------------------------
# Mesh-aware arm — every hot-path op over a sharded data axis
# ---------------------------------------------------------------------------
#
# The sharded arm is keyed like the single-device registry plus a
# PARTITION STRATEGY (DESIGN.md §9): "query" shards the batch rows against
# a replicated model (zero merge collective — the paper's
# Independent-Tasks framing); "reference" shards the model-side axis (kNN
# rows / centroids / classes / components / trees) and merges per-shard
# partials (the paper's OP3 master-merge).  Inside the shard_map each
# shard runs the SAME registry-dispatched kernel (fused / blocked / ref
# still selected per per-shard shape, and REPRO_BACKEND / ``path=`` still
# override).  Implementations live in core/cluster.py; the deferred
# imports break the core -> dispatch -> cluster -> core cycle.

STRATEGY_ENV_VAR = "REPRO_SHARD_STRATEGY"
STRATEGY_NAMES = ("single", "query", "reference")
# the arm `Estimator.predict_batch_sharded_fn(mesh)` resolves to when no
# strategy is named — the pre-strategy-dispatch behaviour of each estimator
DEFAULT_STRATEGY = {"knn": "reference"}

_SHARDED: Dict[Tuple[str, str, str], Callable] = {}


def register_sharded(algorithm: str, op: str, strategy: str = "query"):
    assert strategy in STRATEGY_NAMES, strategy

    def deco(fn):
        _SHARDED[(algorithm, op, strategy)] = fn
        return fn

    return deco


def sharded(algorithm: str, op: str,
            strategy: Optional[str] = None) -> Callable:
    """The mesh-aware executor for ``(algorithm, op)`` under ``strategy``
    (None = the algorithm's legacy default arm); raises KeyError for ops
    with no such sharded arm (mirrors ``resolve`` for unknown keys)."""
    if strategy is None:
        strategy = DEFAULT_STRATEGY.get(algorithm, "query")
    key = (algorithm, op, strategy)
    if key not in _SHARDED:
        raise KeyError(f"no sharded arm for {key}; "
                       f"known: {sorted(_SHARDED)}")
    return _SHARDED[key]


def sharded_registered() -> Tuple[Tuple[str, str, str], ...]:
    """(algorithm, op, strategy) keys with a mesh-aware arm, for docs and
    tests."""
    return tuple(sorted(_SHARDED))


def strategy_env_override() -> Optional[str]:
    """``REPRO_SHARD_STRATEGY``: pin the serving partition strategy for A/B
    runs and tests, same contract as ``REPRO_BACKEND`` (a typo must fail,
    not silently benchmark the default).  ``auto`` defers to the cost
    model — the explicit spelling of the default."""
    v = os.environ.get(STRATEGY_ENV_VAR, "").strip()
    if not v or v == "auto":
        return None
    if v not in STRATEGY_NAMES:
        raise ValueError(f"{STRATEGY_ENV_VAR}={v!r} is not one of "
                         f"{('auto',) + STRATEGY_NAMES}")
    return v


def resolve_strategy(algorithm: str, *, bucket: int, n_shards: int,
                     strategy: Optional[str] = None,
                     policy: Optional[PrecisionPolicy] = None,
                     shape: Optional[Dict[str, int]] = None,
                     quantized: Optional[bool] = None,
                     cost_model=None) -> str:
    """Pick the serving partition strategy for one (algorithm, bucket,
    mesh) cell.

    Precedence mirrors ``resolve``: explicit ``strategy=`` >
    ``REPRO_SHARD_STRATEGY`` env > the active CostModel (Eq. 15's
    t_par/c + t_seq per partition — analytic by default, measured
    us/query rows when calibrated).  Quantized arms (int8 policy or
    ``REPRO_BACKEND=quant``) exclude "reference" from the model: the int8
    lattices derive from the model-side operand, which a model partition
    would chunk."""
    if strategy is not None and strategy != "auto":
        if strategy not in STRATEGY_NAMES:
            raise ValueError(f"strategy={strategy!r} is not one of "
                             f"{('auto',) + STRATEGY_NAMES}")
        return strategy
    env = strategy_env_override()
    if env is not None:
        return env
    precision = _precision_mod()
    if quantized is None:
        quantized = ((policy is not None and policy.quantized)
                     or env_override() == "quant")
    cm = cost_model if cost_model is not None else active_cost_model()
    if cm.calibrated:
        base = (policy or DEFAULT_POLICY).name.split("@")[0]
        costs = cm.strategy_costs(
            algorithm, bucket=bucket, n_shards=n_shards, shape=shape,
            quantized=quantized,
            tier=precision.tier_for(base, quantized=quantized))
    else:
        backend = precision.BACKENDS[
            (policy or DEFAULT_POLICY).cost_backend]
        costs = precision.serve_strategy_costs(
            algorithm, bucket=bucket, n_shards=n_shards, shape=shape,
            backend=backend, quantized=quantized)
    # the model only costs strategies the algorithm can execute: drop
    # candidates with no registered sharded arm (ANN has no "reference"
    # partition — its inverted lists address global row ids)
    for cand in [s for s in costs if s != "single"]:
        if not any(a == algorithm and st == cand for a, _, st in _SHARDED):
            del costs[cand]
    return precision.pick_strategy(costs)


@register_sharded("knn", "distance_topk", "reference")
def distance_topk_sharded(a, c, k, *, mesh, axis="data", policy=None,
                          path=None, merge=None):
    """Reference set row-sharded, per-shard fused kernel, candidate merge
    (hierarchical butterfly on power-of-two meshes); bit-equal to
    ``distance_topk``."""
    from repro.core import cluster
    return cluster.distance_topk_shardmap(a, c, k, mesh, axis,
                                          policy=policy, path=path,
                                          merge=merge)


@register_sharded("knn", "distance_topk", "query")
def distance_topk_query_sharded(a, c, k, *, mesh, axis="data", policy=None,
                                path=None):
    from repro.core import cluster
    return cluster.distance_topk_query_shardmap(a, c, k, mesh, axis,
                                                policy=policy, path=path)


@register_sharded("ann", "adc_topk", "query")
def adc_topk_query_sharded(qlut, codes, cand_ids, k, *, mesh, axis="data",
                           policy=None, path=None):
    """Pure query partition: every ADC operand is query-row-indexed, so
    shards run the whole op on their rows with NO merge collective."""
    from repro.core import cluster
    return cluster.adc_topk_query_shardmap(qlut, codes, cand_ids, k, mesh,
                                           axis, policy=policy, path=path)


@register_sharded("kmeans", "distance_argmin", "query")
def distance_argmin_sharded(a, c, *, mesh, axis="data", policy=None,
                            path=None):
    from repro.core import cluster
    return cluster.distance_argmin_shardmap(a, c, mesh, axis,
                                            policy=policy, path=path)


@register_sharded("kmeans", "distance_argmin", "reference")
def distance_argmin_centroid_sharded(a, c, *, mesh, axis="data",
                                     policy=None, path=None):
    from repro.core import cluster
    return cluster.distance_argmin_centroid_shardmap(a, c, mesh, axis,
                                                     policy=policy,
                                                     path=path)


@register_sharded("gnb", "scores", "query")
def gnb_scores_sharded(X, mu, var, log_prior, *, mesh, axis="data",
                       policy=None, path=None):
    from repro.core import cluster
    return cluster.gnb_scores_shardmap(X, mu, var, log_prior, mesh, axis,
                                       policy=policy, path=path)


@register_sharded("gnb", "scores", "reference")
def gnb_scores_class_sharded(X, mu, var, log_prior, *, mesh, axis="data",
                             policy=None, path=None):
    from repro.core import cluster
    return cluster.gnb_scores_class_shardmap(X, mu, var, log_prior, mesh,
                                             axis, policy=policy, path=path)


@register_sharded("gmm", "responsibilities", "query")
def gmm_responsibilities_sharded(mu, var, log_pi, X, *, mesh, axis="data",
                                 policy=None, path=None, n_cores=8):
    from repro.core import cluster
    return cluster.gmm_responsibilities_shardmap(mu, var, log_pi, X, mesh,
                                                 axis, policy=policy,
                                                 path=path, n_cores=n_cores)


@register_sharded("gmm", "responsibilities", "reference")
def gmm_responsibilities_comp_sharded(mu, var, log_pi, X, *, mesh,
                                      axis="data", policy=None, path=None,
                                      n_cores=8):
    from repro.core import cluster
    return cluster.gmm_responsibilities_comp_shardmap(
        mu, var, log_pi, X, mesh, axis, policy=policy, path=path,
        n_cores=n_cores)


@register_sharded("rf", "forest_votes", "query")
def forest_votes_sharded(forest, X, *, mesh, axis="data", policy=None,
                         path=None, n_cores=8):
    from repro.core import cluster
    return cluster.forest_votes_shardmap(forest, X, mesh, axis,
                                         policy=policy, path=path,
                                         n_cores=n_cores)


@register_sharded("rf", "forest_votes", "reference")
def forest_votes_tree_sharded(forest, X, *, mesh, axis="data", policy=None,
                              path=None, n_cores=8):
    from repro.core import cluster
    return cluster.forest_votes_tree_shardmap(forest, X, mesh, axis,
                                              policy=policy, path=path,
                                              n_cores=n_cores)


# ---------------------------------------------------------------------------
# Grouped arm — one vmapped launch over a (G, ...) stacked model group
# ---------------------------------------------------------------------------
#
# Multi-tenant serving (serving/model_store.py, DESIGN.md §11): estimator
# params are NamedTuple pytrees, so G same-shape fitted models stack into
# one leading axis and a whole model group serves as ONE kernel launch —
# ``jax.vmap`` of the estimator's pure ``(params, X) -> (preds, aux)``
# batch fn over (stacked params, (G, B, d) queries).  The arm is
# registered per algorithm (mirroring the sharded registry) so an
# algorithm whose params CANNOT stack — ANN's inverted lists are ragged
# per fit — refuses loudly instead of vmapping garbage.  Each vmapped
# lane runs the registry-dispatched kernel unchanged, so the grouped
# launch is bit-equal per tenant to the per-model loop (the conformance
# suite pins this for all five algorithms).

_GROUPED: Dict[str, Callable] = {}


def register_grouped(algorithm: str):
    def deco(fn):
        _GROUPED[algorithm] = fn
        return fn

    return deco


def grouped(algorithm: str) -> Callable:
    """The grouped-launch builder for ``algorithm``: called as
    ``grouped(alg)(batch_fn, params_axes)`` it returns a pure
    ``(stacked_params, Xg) -> (preds (G, B), aux (G, B, ...))`` executor.
    ``params_axes`` is the vmap in_axes pytree — 0 on array leaves, None
    on static metadata leaves (e.g. ``n_class``) — and MUST be computed
    from concrete params (under a trace every leaf looks like an array).
    Raises KeyError for algorithms with no grouped arm (mirrors
    ``sharded`` for unknown keys)."""
    if algorithm not in _GROUPED:
        raise KeyError(f"no grouped serving arm for {algorithm!r}; "
                       f"known: {sorted(_GROUPED)}")
    return _GROUPED[algorithm]


def grouped_registered() -> Tuple[str, ...]:
    """Algorithms with a grouped (multi-tenant vmapped) arm, for docs and
    tests."""
    return tuple(sorted(_GROUPED))


def _vmap_group(batch_fn: Callable, params_axes) -> Callable:
    import jax
    return jax.vmap(batch_fn, in_axes=(params_axes, 0))


# all five dense-param estimators stack; each registration is the explicit
# statement "this algorithm's param pytree is shape-stable across fits"
register_grouped("knn")(_vmap_group)
register_grouped("kmeans")(_vmap_group)
register_grouped("gnb")(_vmap_group)
register_grouped("gmm")(_vmap_group)
register_grouped("rf")(_vmap_group)    # after pad_nodes normalization
