"""CC parameter tuners: gradient (soft model), ES and BO (hard model).

The pieces:

  * :class:`TunableParam` / :class:`ParamBox` — a bounded, optionally
    log-scaled search box over CC constants.  Each knob names both its
    ``StepParams`` leaves (what a traced rollout reads, e.g.
    ``"mark.cp_kmin"``) and its config paths (what a human sets, e.g.
    ``"dcqcn.kmin"``); ``apply`` swaps tuned values into a ``StepParams``
    pytree inside a trace, ``to_spec`` writes the same values back into
    a frozen ``CCSpec`` and *asserts* the two routes agree through
    ``step_params`` — the box cannot silently tune a different constant
    than it reports.
  * :class:`TuneProblem` / :class:`Evaluator` — one (config, scenario,
    objective) instance.  ``value_and_grad`` differentiates the
    temperature-smoothed rollout (``repro.tune.soft``) through the
    dt-scan — the whole thing is ONE cached executable in
    ``SWEEP_EXEC_CACHE`` (AOT-compiled, keyed like a sweep launch).
    ``hard_values`` scores parameter batches on the exact hard model by
    riding ``Sweep.run`` — the population IS a sweep, so ES/BO
    evaluations vectorise onto the existing one-jit vmap run axis and
    hit the same executable cache.
  * :class:`GradTuner` — Adam (inlined; no external optimiser dep) on
    an unconstrained reparameterisation of the box, ascending
    ``jax.grad`` of the soft objective.
  * :class:`ESTuner` — antithetic evolution strategies on the hard
    model (no smoothing bias, works for the integer-ish knobs gradients
    cannot see).
  * :class:`BOTuner` — Bayesian optimisation: a fixed-hyperparameter
    RBF Gaussian process on the unit box with Thompson-sampling batch
    proposals.

All tuners checkpoint through ``repro.ckpt`` (``ckpt_dir=...``): host
state is float64 numpy and per-iteration randomness is keyed
``default_rng([seed, it])``, so a killed-and-resumed run replays the
exact trajectory of an uninterrupted one (bit-exact, tested).
"""

from __future__ import annotations

import dataclasses
import operator
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.experiments import SWEEP_EXEC_CACHE, ScenarioSpec, Sweep
from repro.core.fluid import (Scenario, check_routing_paths, fluid_step,
                              init_state, scenario_device, step_params)
from repro.core.params import CCConfig, CCSpec
from repro.core.simulator import _resolve_steps, decimating_scan

from . import objectives

# ---------------------------------------------------------------------------
# the search box
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TunableParam:
    """One bounded knob, named on both sides of ``step_params``.

    ``leaves`` are dotted ``StepParams`` paths (``"mark.cp_kmin"``,
    ``"react.rp_g"``, or a top-level field like ``"xoff"``) — what
    ``ParamBox.apply`` overrides inside a traced rollout.
    ``spec_paths`` are the matching dotted config paths
    (``"dcqcn.kmin"``) written by ``to_spec``.  Several paths tune as
    one knob (DCQCN's step marking uses one V for kmin = kmax).
    ``log=True`` searches the decade range geometrically.
    """

    name: str
    leaves: tuple
    spec_paths: tuple
    lo: float
    hi: float
    log: bool = False

    def __post_init__(self):
        if not (0 < self.lo < self.hi) and self.log:
            raise ValueError(f"{self.name}: log scale needs 0 < lo < hi")
        if self.lo >= self.hi:
            raise ValueError(f"{self.name}: empty range [{self.lo}, "
                             f"{self.hi}]")


def _sigmoid(x, xp):
    return 1.0 / (1.0 + xp.exp(-x))


def _replace_many(cfg, updates: dict):
    """All dotted-path writes in one ``dataclasses.replace`` per parent.

    Sequential single-path writes would trip ``__post_init__``
    validation on transient states (e.g. raising kmin above the old
    kmax before kmax is written); batching means validators only ever
    see the final combination.
    """
    direct, nested = {}, {}
    for path, v in updates.items():
        head, _, rest = path.partition(".")
        if rest:
            nested.setdefault(head, {})[rest] = v
        else:
            direct[head] = v
    for head, sub in nested.items():
        direct[head] = _replace_many(getattr(cfg, head), sub)
    return dataclasses.replace(cfg, **direct)


def _get_leaf(par, path: str):
    head, _, rest = path.partition(".")
    v = getattr(par, head)
    return v[rest] if rest else v


def _set_leaf(par, path: str, value):
    head, _, rest = path.partition(".")
    if rest:
        fam = dict(getattr(par, head))
        if rest not in fam:
            raise KeyError(f"StepParams.{head} has no leaf {rest!r} "
                           f"(have {sorted(fam)})")
        fam[rest] = value
        return par._replace(**{head: fam})
    return par._replace(**{head: value})


@dataclasses.dataclass(frozen=True)
class ParamBox:
    """A tuple of :class:`TunableParam` — the tuner's search space.

    Optimisers work in unconstrained theta-space; ``values`` maps theta
    through a sigmoid onto each knob's (lin or log) range, so every
    theta is feasible and bounds never need projection.
    """

    params: tuple

    def __post_init__(self):
        names = [p.name for p in self.params]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate knob names in box: {names}")

    @property
    def d(self) -> int:
        return len(self.params)

    @property
    def names(self) -> tuple:
        return tuple(p.name for p in self.params)

    def signature(self) -> tuple:
        """Hashable identity for executable-cache keys."""
        return tuple((p.name, p.leaves, p.spec_paths, p.lo, p.hi, p.log)
                     for p in self.params)

    def values(self, theta, xp=jnp):
        """[d] theta -> [d] physical values (jnp inside traces, np on
        host — same formulas, so host round-trips match the trace)."""
        u = _sigmoid(theta, xp)
        lo = xp.asarray([p.lo for p in self.params], theta.dtype)
        hi = xp.asarray([p.hi for p in self.params], theta.dtype)
        is_log = xp.asarray([p.log for p in self.params], bool)
        lin = lo + (hi - lo) * u
        geo = xp.exp(xp.log(lo) + (xp.log(hi) - xp.log(lo)) * u)
        return xp.where(is_log, geo, lin)

    def apply(self, par, theta):
        """StepParams with this box's leaves overridden from theta."""
        vals = self.values(jnp.asarray(theta, jnp.float32))
        for tp, v in zip(self.params, vals):
            for leaf in tp.leaves:
                _get_leaf(par, leaf)          # raises on a bad path
                par = _set_leaf(par, leaf, v)
        return par

    def encode(self, cfg: "CCConfig | CCSpec") -> np.ndarray:
        """theta [d] f64 whose values reproduce the config's current
        settings (clipped just inside the box)."""
        spec = cfg.to_spec()
        theta = np.zeros(self.d)
        for i, tp in enumerate(self.params):
            v = float(operator.attrgetter(tp.spec_paths[0])(spec))
            if tp.log:
                u = (np.log(max(v, tp.lo)) - np.log(tp.lo)) \
                    / (np.log(tp.hi) - np.log(tp.lo))
            else:
                u = (v - tp.lo) / (tp.hi - tp.lo)
            u = float(np.clip(u, 1e-4, 1 - 1e-4))
            theta[i] = np.log(u / (1 - u))
        return theta

    def to_spec(self, cfg: "CCConfig | CCSpec", theta) -> CCSpec:
        """The config with this theta's values written back.

        Consistency-checked: the spec is flattened through
        ``step_params`` and every tuned ``StepParams`` leaf must equal
        the value ``apply`` would have used — so what a tuner reports
        is provably what its rollouts ran.
        """
        spec = cfg.to_spec()
        vals = self.values(np.asarray(theta, np.float32), xp=np)
        updates = {path: float(v)
                   for tp, v in zip(self.params, vals)
                   for path in tp.spec_paths}
        spec = _replace_many(spec, updates)
        par = step_params(spec)
        for tp, v in zip(self.params, vals):
            for leaf in tp.leaves:
                got = float(np.asarray(_get_leaf(par, leaf)))
                if not np.isclose(got, float(v), rtol=1e-5, atol=0):
                    raise AssertionError(
                        f"box inconsistency: {tp.name}: spec path(s) "
                        f"{tp.spec_paths} produced StepParams leaf "
                        f"{leaf} = {got}, expected {float(v)}")
        return spec


def dcqcn_box() -> ParamBox:
    """The DCQCN knobs the paper's sensitivity analysis walks: the
    marking threshold V (kmin = kmax, step marking), the rate-decrease
    aggressiveness, the alpha gain g and the additive-increase slope."""
    return ParamBox((
        TunableParam("V", ("mark.cp_kmin",),
                     ("dcqcn.kmin", "dcqcn.kmax"), 2e3, 2.56e5, log=True),
        TunableParam("rdf", ("react.rp_rdf",),
                     ("dcqcn.rate_decrease_factor",), 0.05, 1.0),
        TunableParam("g", ("react.rp_g",), ("dcqcn.g",),
                     1.0 / 1024, 0.25, log=True),
        TunableParam("rai", ("react.rp_rai",), ("dcqcn.rai",),
                     1e6, 2e8, log=True),
    ))


def rev_box() -> ParamBox:
    """The paper-scheme (ECP/ENP/ERP) knobs: detection threshold,
    settle fraction, recovery slope and hold-down."""
    return ParamBox((
        TunableParam("thresh", ("mark.ecp_thresh",),
                     ("rev.detect_threshold",), 4e3, 1.28e5, log=True),
        TunableParam("settle", ("react.erp_settle",),
                     ("rev.erp_settle",), 0.5, 1.0),
        TunableParam("rai", ("react.erp_rai",),
                     ("rev.erp_rai",), 1e11, 5e13, log=True),
        TunableParam("hold", ("react.erp_hold",),
                     ("rev.erp_hold",), 5e-6, 5e-4, log=True),
    ))


def box_for(cfg: "CCConfig | CCSpec") -> ParamBox:
    """Default box for a config, keyed on its reaction stage."""
    reaction = cfg.to_spec().reaction
    boxes = {"rp": dcqcn_box, "erp": rev_box}
    if reaction not in boxes:
        raise ValueError(
            f"no default ParamBox for reaction {reaction!r}; pass an "
            f"explicit box= (have defaults for {sorted(boxes)})")
    return boxes[reaction]()


# ---------------------------------------------------------------------------
# the problem + its evaluators
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TuneProblem:
    """One tuning instance: which config, on which workload, scored
    how, over which knobs."""

    cfg: "CCConfig | CCSpec"
    scenario: "Scenario | ScenarioSpec"
    objective: "str | dict | Callable" = "default"
    box: ParamBox = None
    n_steps: int = 2000
    trace_every: int = 50

    def __post_init__(self):
        if self.box is None:
            self.box = box_for(self.cfg)


class _TraceShim:
    """Host-side stand-in for the stacked TraceSample (objectives only
    read ``.ctrl``)."""

    def __init__(self, ctrl):
        self.ctrl = np.asarray(ctrl, np.float32)


class Evaluator:
    """Compiled evaluation paths for one :class:`TuneProblem`."""

    def __init__(self, problem: TuneProblem):
        self.problem = problem
        self.box = problem.box
        cfg = problem.cfg
        self.spec: CCSpec = cfg.to_spec()
        scn = problem.scenario
        if isinstance(scn, ScenarioSpec):
            scn = scn.build(cfg)
        check_routing_paths(cfg, scn)
        self.scn: Scenario = scn
        self.sd = scenario_device(scn)
        self.st0 = init_state(scn, cfg)
        self.par0 = step_params(cfg)
        self.n_samples, self.k = _resolve_steps(
            cfg, problem.n_steps, problem.trace_every)
        self.dt = float(cfg.sim.dt)
        self.n_sw = scn.n_switches
        self.horizon = self.n_samples * self.k * self.dt
        self.ctx = objectives.make_ctx(
            scn, cfg.link.line_rate, self.horizon, self.dt)
        self.obj_fn, self.obj_sig = objectives.resolve(problem.objective)
        self._vag = None

    # -- soft path: one AOT-compiled value_and_grad -------------------------

    def _vag_exec(self):
        if self._vag is not None:
            return self._vag
        n_samples, k, dt, n_sw = (self.n_samples, self.k, self.dt,
                                  self.n_sw)
        box, obj_fn = self.box, self.obj_fn
        args = (jnp.zeros((box.d,), jnp.float32),
                jnp.asarray(0.0, jnp.float32),
                self.st0, self.sd, self.par0, self.ctx)
        leaves, treedef = jax.tree.flatten(args)
        shapes = tuple((tuple(x.shape), x.dtype.name) for x in leaves)
        key = ("tune_vag", box.signature(), self.obj_sig,
               n_samples, k, dt, n_sw, treedef, shapes)

        def build():
            def loss(theta, tau, st0, sd, par0, ctx):
                par = box.apply(par0, theta)
                par = par._replace(
                    temperature=jnp.asarray(tau, jnp.float32))
                step = lambda s: fluid_step(
                    s, sd, par, dt=dt, n_switches=n_sw,
                    reduce="fused", dense_blocks=())
                final, tr = decimating_scan(step, st0, n_samples, k, dt)
                return obj_fn(final, tr, ctx)

            return jax.jit(jax.value_and_grad(loss)) \
                .lower(*args).compile()

        self._vag = SWEEP_EXEC_CACHE.get_or_build(key, build)
        return self._vag

    def value_and_grad(self, theta, temperature: float):
        """(soft objective, d(objective)/d(theta)) at one theta.

        ``temperature`` is traced data — every call reuses one cached
        executable; 0.0 evaluates the exact hard model (with the
        gradient of its soft limit)."""
        v, g = self._vag_exec()(
            jnp.asarray(theta, jnp.float32),
            jnp.asarray(temperature, jnp.float32),
            self.st0, self.sd, self.par0, self.ctx)
        return float(v), np.asarray(g, np.float64)

    # -- hard path: populations ride the Sweep engine -----------------------

    def hard_values(self, thetas) -> np.ndarray:
        """[P] exact hard-model objective for a theta batch.

        Each theta becomes a ``CCSpec`` (consistency-checked) and the
        batch runs as ONE ``Sweep`` launch — the population shares the
        sweep executable cache, so repeated generations of the same
        shape never recompile.  Values come from the same objective
        function the soft path uses, applied to the hard traces.
        """
        thetas = np.atleast_2d(np.asarray(thetas, np.float64))
        points = [(f"t{i}", self.box.to_spec(self.spec, th), self.scn)
                  for i, th in enumerate(thetas)]
        res = Sweep(points).run(
            n_steps=self.problem.n_steps, trace_every=self.k)
        return np.asarray([self.hard_objective(res[i])
                           for i in range(len(thetas))])

    def hard_objective(self, sim_result) -> float:
        """The tuner objective evaluated on a finished hard run."""
        val = self.obj_fn(sim_result.final,
                          _TraceShim(sim_result.ctrl), self.ctx)
        return float(np.asarray(val))


# ---------------------------------------------------------------------------
# checkpoint plumbing (repro.ckpt; host f64 state, bit-exact resume)
# ---------------------------------------------------------------------------


def _ckpt_save(ckpt_dir, it, state: dict):
    from repro.ckpt import save_checkpoint
    save_checkpoint(ckpt_dir, it, state, extra={"it": it})


def _ckpt_load(ckpt_dir):
    """(state, it) from the latest committed checkpoint, or (None, 0)."""
    from repro.ckpt import latest_step, load_checkpoint
    if ckpt_dir is None or latest_step(ckpt_dir) is None:
        return None, 0
    tree, extra = load_checkpoint(ckpt_dir)
    return tree, int(extra["it"])


@dataclasses.dataclass
class TuneTrace:
    """Everything a tuner evaluated: [n, d] thetas, [n] objective
    values (soft for :class:`GradTuner`, hard for ES/BO) and metadata.
    ``best`` is the argmax theta — candidates for the *decision* should
    still be re-scored on the hard model (``pareto.autotune`` does)."""

    theta: np.ndarray
    value: np.ndarray
    meta: dict

    @property
    def best(self) -> np.ndarray:
        return self.theta[int(np.argmax(self.value))]


# ---------------------------------------------------------------------------
# tuners
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GradTuner:
    """Adam ascent on the temperature-smoothed objective.

    The gradient flows through the full dt-scan (soft gates, see
    ``repro.tune.soft``); Adam is inlined (bias-corrected, standard
    constants) so the tuner has no optimiser dependency.  ``anneal``
    decays the temperature geometrically to ``temperature_final`` over
    the run — late iterations score an almost-hard model.
    """

    iters: int = 40
    lr: float = 0.15
    temperature: float = 0.06
    temperature_final: float = None     # None = constant temperature
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8

    def _tau(self, it: int) -> float:
        if self.temperature_final is None or self.iters <= 1:
            return self.temperature
        frac = it / (self.iters - 1)
        return float(self.temperature
                     * (self.temperature_final / self.temperature) ** frac)

    def run(self, problem: TuneProblem, *, theta0=None, seed: int = 0,
            ckpt_dir: str = None, ckpt_every: int = 0) -> TuneTrace:
        ev = problem if isinstance(problem, Evaluator) else \
            Evaluator(problem)
        d = ev.box.d
        theta = np.asarray(theta0, np.float64) if theta0 is not None \
            else ev.box.encode(ev.spec)
        m, v = np.zeros(d), np.zeros(d)
        hist_t, hist_v = [], []
        state, start = _ckpt_load(ckpt_dir)
        if state is not None:
            theta, m, v = (np.asarray(state[k])
                           for k in ("theta", "m", "v"))
            hist_t = list(np.asarray(state["hist_t"]))
            hist_v = list(np.asarray(state["hist_v"]))
        for it in range(start, self.iters):
            val, g = ev.value_and_grad(theta, self._tau(it))
            hist_t.append(theta.copy())
            hist_v.append(val)
            m = self.beta1 * m + (1 - self.beta1) * g
            v = self.beta2 * v + (1 - self.beta2) * g * g
            mh = m / (1 - self.beta1 ** (it + 1))
            vh = v / (1 - self.beta2 ** (it + 1))
            theta = theta + self.lr * mh / (np.sqrt(vh) + self.eps)
            if ckpt_dir and ckpt_every and (it + 1) % ckpt_every == 0:
                _ckpt_save(ckpt_dir, it + 1, {
                    "theta": theta, "m": m, "v": v,
                    "hist_t": np.asarray(hist_t),
                    "hist_v": np.asarray(hist_v)})
        # score the final iterate so the trajectory includes it
        val, _ = ev.value_and_grad(theta, self._tau(self.iters - 1))
        hist_t.append(theta.copy())
        hist_v.append(val)
        return TuneTrace(np.asarray(hist_t), np.asarray(hist_v),
                         {"method": "grad", "iters": self.iters,
                          "temperature": self.temperature})


@dataclasses.dataclass
class ESTuner:
    """Antithetic evolution strategies on the exact hard model.

    Each generation draws ``pop/2`` Gaussian directions, scores the
    +/- pair batch as ONE sweep launch, and ascends the score-weighted
    direction average (normalised by the generation's value spread).
    Per-generation randomness is keyed ``default_rng([seed, it])`` so a
    checkpoint resume replays the identical trajectory.
    """

    iters: int = 20
    pop: int = 16
    sigma: float = 0.25
    lr: float = 0.3

    def run(self, problem: TuneProblem, *, theta0=None, seed: int = 0,
            ckpt_dir: str = None, ckpt_every: int = 0) -> TuneTrace:
        if self.pop % 2:
            raise ValueError("ESTuner.pop must be even (antithetic)")
        ev = problem if isinstance(problem, Evaluator) else \
            Evaluator(problem)
        d = ev.box.d
        half = self.pop // 2
        theta = np.asarray(theta0, np.float64) if theta0 is not None \
            else ev.box.encode(ev.spec)
        hist_t, hist_v = [], []
        state, start = _ckpt_load(ckpt_dir)
        if state is not None:
            theta = np.asarray(state["theta"])
            hist_t = list(np.asarray(state["hist_t"]))
            hist_v = list(np.asarray(state["hist_v"]))
        for it in range(start, self.iters):
            rng = np.random.default_rng([seed, it])
            eps = rng.standard_normal((half, d))
            cand = np.concatenate(
                [theta + self.sigma * eps, theta - self.sigma * eps])
            vals = ev.hard_values(cand)
            hist_t.extend(cand)
            hist_v.extend(vals)
            adv = vals[:half] - vals[half:]
            scale = max(float(vals.std()), 1e-9)
            g = (adv[:, None] * eps).sum(0) / (self.pop * self.sigma
                                               * scale)
            theta = theta + self.lr * g
            if ckpt_dir and ckpt_every and (it + 1) % ckpt_every == 0:
                _ckpt_save(ckpt_dir, it + 1, {
                    "theta": theta,
                    "hist_t": np.asarray(hist_t),
                    "hist_v": np.asarray(hist_v)})
        final_val = ev.hard_values(theta[None])[0]
        hist_t.append(theta.copy())
        hist_v.append(final_val)
        return TuneTrace(np.asarray(hist_t), np.asarray(hist_v),
                         {"method": "es", "iters": self.iters,
                          "pop": self.pop, "sigma": self.sigma})


def _rbf(a: np.ndarray, b: np.ndarray, ls: float) -> np.ndarray:
    d2 = ((a[:, None, :] - b[None, :, :]) ** 2).sum(-1)
    return np.exp(-0.5 * d2 / ls ** 2)


@dataclasses.dataclass
class BOTuner:
    """Thompson-sampling Bayesian optimisation on the unit box.

    A fixed-hyperparameter RBF GP (lengthscale on the [0, 1]^d encoded
    box, values standardised per fit) is cheap, dependency-free and
    deterministic; each iteration draws ``q`` joint posterior samples
    at ``cand`` uniform candidates and evaluates the batch of argmaxes
    as one sweep launch.  Exploration comes from posterior variance,
    not a tuned acquisition.
    """

    iters: int = 12
    init: int = 6
    q: int = 2
    cand: int = 256
    lengthscale: float = 0.35
    noise: float = 1e-4

    @staticmethod
    def _logit(u: np.ndarray) -> np.ndarray:
        u = np.clip(u, 1e-4, 1 - 1e-4)
        return np.log(u / (1 - u))

    def _propose(self, X, y, rng) -> np.ndarray:
        """[<=q, d] unit-box batch from joint Thompson samples."""
        C = rng.uniform(size=(self.cand, X.shape[1]))
        mu, sd = y.mean(), max(float(y.std()), 1e-9)
        ys = (y - mu) / sd
        K = _rbf(X, X, self.lengthscale) + self.noise * np.eye(len(X))
        L = np.linalg.cholesky(K)
        alpha = np.linalg.solve(L.T, np.linalg.solve(L, ys))
        Kc = _rbf(C, X, self.lengthscale)
        mean = Kc @ alpha
        v = np.linalg.solve(L, Kc.T)
        cov = _rbf(C, C, self.lengthscale) - v.T @ v
        Lc = np.linalg.cholesky(cov + 1e-8 * np.eye(self.cand))
        z = rng.standard_normal((self.cand, self.q))
        picks = np.unique(np.argmax(mean[:, None] + Lc @ z, axis=0))
        return C[picks]

    def run(self, problem: TuneProblem, *, theta0=None, seed: int = 0,
            ckpt_dir: str = None, ckpt_every: int = 0) -> TuneTrace:
        ev = problem if isinstance(problem, Evaluator) else \
            Evaluator(problem)
        d = ev.box.d
        state, start = _ckpt_load(ckpt_dir)
        if state is not None:
            X = np.asarray(state["X"])
            y = np.asarray(state["y"])
        else:
            rng = np.random.default_rng([seed, 0])
            u0 = _sigmoid(np.asarray(
                theta0 if theta0 is not None else ev.box.encode(ev.spec),
                np.float64), np)
            X = np.concatenate(
                [u0[None], rng.uniform(size=(max(self.init - 1, 0), d))])
            y = ev.hard_values(self._logit(X))
        for it in range(start + 1, self.iters + 1):
            rng = np.random.default_rng([seed, it])
            U = self._propose(X, y, rng)
            vals = ev.hard_values(self._logit(U))
            X = np.concatenate([X, U])
            y = np.concatenate([y, vals])
            if ckpt_dir and ckpt_every and it % ckpt_every == 0:
                _ckpt_save(ckpt_dir, it, {"X": X, "y": y})
        return TuneTrace(self._logit(X), y,
                         {"method": "bo", "iters": self.iters,
                          "q": self.q, "cand": self.cand})


TUNERS = {"grad": GradTuner, "es": ESTuner, "bo": BOTuner}
