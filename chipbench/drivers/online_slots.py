"""CoCaR-OL (Alg. 2) deciding each BS's cached submodels slot after slot,
as an operator runs it online.

The traffic is drawn slot by slot as the loop needs it: each slot's
``(n_bs, n_models)`` request counts are Poisson with mean
``requests_per_slot / n_bs`` times each BS's Zipf popularity, from a
generator keyed on (seed, slot); every ``pop_change_every`` slots each
BS's Zipf ranks are permuted anew, from a generator keyed on (seed,
epoch).  The BSs that each slot's ``rounds`` adjustments visit are drawn
the same way.  So no whole count tensor is made, and any slot can be drawn
again for the check.

The window: each step runs one slot through the program's compiled
CoCaR-OL scan (``traces.engine``; a chunk of one slot from the carried
state, span ``slot``), then pulls the slot's decisions (cached level,
downloads in flight, targets) to the host, as an operator acts on them.
``decide_ms`` is the window's wall time over the slots decided.

The check, after the window: the program runs its first ``check_slots``
slots again from a fresh state with ``record_states``; its per-slot QoE
must equal the window's own (``slot_qoe_rerun_differing``), which ties the
recorded states to the timed run.  Then the plain reference
(``reference/online.py``) replays the same slots from the same counts and
stream: ``states_differing`` counts the (slot, BS, model) entries of the
cached level, download flag and target that differ, and
``slot_qoe_rel_error`` the largest difference of a slot's QoE, relative
to the largest slot QoE of either.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from chipbench.drivers.offline_windows import _MEC_KEYS

#: OnlineConfig fields the mix sets
_ONLINE_KEYS = ("slot_s", "rounds", "dT_past", "dT_future", "alpha",
                "gamma", "partition", "pop_change_every", "knap_units")


class SlotTraffic:
    """Slot ``t``'s request counts and adjusted BSs, each drawn from its
    own generator keyed on the run's seed and the slot."""

    def __init__(self, mix: dict, cfg: dict, seed: int):
        self.seed = int(seed)
        self.N, self.M = int(cfg["n_bs"]), int(cfg["n_models"])
        self.rounds = int(mix["rounds"])
        self.change = int(mix["pop_change_every"])
        rank = 1.0 / np.arange(1, self.M + 1) ** float(cfg["zipf"])
        self.zipf = rank / rank.sum()
        self.scale = float(mix["requests_per_slot"]) / self.N
        self._epoch, self._pop = -1, None

    def popularity(self, epoch: int) -> np.ndarray:
        """(N, M): the Zipf pmf under a rank permutation per BS."""
        rng = np.random.default_rng([self.seed, epoch, 1])
        return np.stack([self.zipf[rng.permutation(self.M)]
                         for _ in range(self.N)])

    def counts(self, t: int) -> np.ndarray:
        epoch = t // self.change if self.change else 0
        if epoch != self._epoch:
            self._epoch, self._pop = epoch, self.popularity(epoch)
        rng = np.random.Generator(np.random.Philox(key=[self.seed, t]))
        return rng.poisson(self.scale * self._pop).astype(np.float64)

    def adjusted(self, t: int) -> np.ndarray:
        rng = np.random.Generator(np.random.Philox(key=[self.seed + 1, t]))
        return rng.integers(0, self.N, size=self.rounds).astype(np.int32)

    def slots(self, t0: int, t1: int):
        """(counts (T, N, M), adjusted (T, rounds)) of slots t0..t1-1."""
        return (np.stack([self.counts(t) for t in range(t0, t1)]),
                np.stack([self.adjusted(t) for t in range(t0, t1)]))


def stream(adjusted: np.ndarray, M: int):
    """The program's decision stream for the slots of ``adjusted`` over
    ``M`` models: the BSs each slot adjusts; the random baseline's draws,
    which CoCaR-OL does not use, are fixed."""
    from repro.traces.generators import DecisionStream

    T, rounds = adjusted.shape
    return DecisionStream(
        adjust_ns=np.asarray(adjusted, np.int32),
        u_model=np.zeros((T, rounds)),
        perms=np.broadcast_to(np.arange(M, dtype=np.int32), (T, rounds, M)),
        u_shrink=np.zeros((T, rounds, M)))


def run_slots(params, state, counts, adjusted, record_states=False):
    """Slots of ``counts`` (T, N, M) and ``adjusted`` (T, rounds) through
    the program's compiled CoCaR-OL scan from ``state``: (state, per-slot
    QoE, hits, diagnostics, recorded states), on the device.  The state
    carries into the next call, so an operator steps slot by slot."""
    import jax

    from repro.traces import engine

    ds = stream(adjusted, counts.shape[-1])
    with jax.enable_x64(True):
        return engine._compiled(False, bool(record_states))(
            params, state, np.asarray(counts, np.float64), ds.adjust_ns,
            ds.u_model, ds.perms, ds.u_shrink,
            engine.POLICIES.index("cocar-ol"))


@dataclass
class State:
    params: object
    ocfg: object
    traffic: SlotTraffic
    topo_seed: int
    qoe: list = field(default_factory=list)
    attempted: int = 0
    failed: int = 0


def setup(ctx) -> State:
    import jax

    t = time.perf_counter()
    from repro.core.online import OnlineConfig
    from repro.mec.scenario import MECConfig, Scenario
    from repro.traces.engine import init_state, make_params

    ctx.phase("program_import", time.perf_counter() - t)
    cfg, mix = ctx.config, ctx.traffic
    topo_seed = ctx.sub_seed("topology")
    mcfg = MECConfig(**{k: cfg[k] for k in _MEC_KEYS},
                     cloud_mbps=float(mix["cloud_mbps"]), seed=topo_seed)
    ocfg = OnlineConfig(**{k: mix[k] for k in _ONLINE_KEYS})
    params = make_params(mcfg, ocfg, Scenario(mcfg))
    # the one shape every slot has, warmed on slots of their own
    t = time.perf_counter()
    warm = SlotTraffic(mix, cfg, ctx.sub_seed("warm-up"))
    st = init_state(params, ocfg.dT_past)
    for k in range(2):
        counts, adjusted = warm.slots(k, k + 1)
        st = run_slots(params, st, counts, adjusted)[0]
        jax.device_get(st)
    ctx.phase("warm_slots", time.perf_counter() - t)
    return State(params=params, ocfg=ocfg, topo_seed=topo_seed,
                 traffic=SlotTraffic(mix, cfg, ctx.sub_seed("traffic")))


def window(ctx, st: State):
    import jax

    from repro.traces.engine import init_state

    state = init_state(st.params, st.ocfg.dT_past)
    tr = st.traffic
    while time.perf_counter() < ctx.deadline:
        t = len(st.qoe)
        st.attempted += 1
        counts, adjusted = tr.slots(t, t + 1)
        with ctx.span("slot"):
            state, qoe, _, _, _ = run_slots(st.params, state, counts,
                                            adjusted)
            # the slot's decisions, on the host for the operator
            _, _, _, qoe = jax.device_get((state.lvl, state.O,
                                           state.target, qoe))
        st.qoe.append(float(qoe[0]))
    ctx.count("decisions", len(st.qoe))


def end_to_end(ctx, st: State) -> dict:
    return {"decide_ms": 1e3 * ctx.window_seconds / len(st.qoe)}


def free(st: State):
    pass


def program_states(ctx, st: State, T: int):
    """The program's first ``T`` slots again from a fresh state, in one
    chunk with the serving states recorded: (states, per-slot QoE)."""
    from repro.traces.engine import init_state

    counts, adjusted = st.traffic.slots(0, T)
    state = init_state(st.params, st.ocfg.dT_past)
    _, qoe, _, _, rec = run_slots(st.params, state, counts, adjusted,
                                  record_states=True)
    states = dict(zip(("lvl", "dl", "target"),
                      (np.asarray(r) for r in rec)))
    return states, np.asarray(qoe)


def compare(states: dict, qoe, ref_states: dict, ref_qoe) -> dict:
    differing = sum(int(np.sum(np.asarray(states[k]) != ref_states[k]))
                    for k in ("lvl", "dl", "target"))
    # a slot's QoE error relative to the largest slot QoE of either: early
    # slots, with nothing cached yet, read 0
    qoe = np.asarray(qoe, np.float64)
    scale = max(np.abs(qoe).max(), np.abs(ref_qoe).max())
    err = np.abs(qoe - ref_qoe).max() / scale if scale else 0.0
    return {"states_differing": float(differing),
            "slot_qoe_rel_error": float(err)}


def _reference(ctx, st: State, T: int, dtype=np.float64, **kw):
    from chipbench.reference import online as R

    counts, adjusted = st.traffic.slots(0, T)
    dep = R.Deployment(ctx.config, ctx.traffic, st.topo_seed, dtype)
    return R.run(dep, counts, adjusted, **kw)


def check(ctx, st: State) -> list:
    T = min(int(ctx.traffic["check_slots"]), len(st.qoe))
    states, qoe = program_states(ctx, st, T)
    rerun = int(np.sum(qoe != np.asarray(st.qoe[:T])))
    out = compare(states, qoe, *_reference(ctx, st, T)) if T else {
        "states_differing": float("inf"), "slot_qoe_rel_error": float("inf")}
    ctx.info["check_slots"] = T
    lim = ctx.traffic["limits"]
    return [{"name": "slot_qoe_rerun_differing", "value": float(rerun),
             "limit": lim["slot_qoe_rerun_differing"]}] + [
        {"name": k, "value": v, "limit": lim[k]} for k, v in out.items()]


def control(ctx, st: State) -> dict:
    """The control's and the planted fault's readings on the checked
    slots: ``f32``, the reference computed in float32; ``no_routine``,
    the reference with the routine download update left out."""
    T = min(int(ctx.traffic["check_slots"]), len(st.qoe))
    states, qoe = program_states(ctx, st, T)
    worst = {}
    for name, kw in (("f32", {"dtype": np.float32}),
                     ("no_routine", {"skip_routine": True})):
        for k, v in compare(states, qoe, *_reference(ctx, st, T,
                                                     **kw)).items():
            worst[f"{name}:{k}"] = v
    return worst
