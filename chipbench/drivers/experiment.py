"""Path driver: the paper's own path, ``repro.api.Experiment.run`` over
the host engine, its scheduler and ``FedLT.round`` on the device.

The engine is wrapped so that simulated time carries from one call of
``Experiment.run`` to the next, so that each round's engine call is timed
(the host span ``engine.run_round``), and so that the window opens and
closes at round boundaries.  Set-up makes the data on the device from
the seed and makes three calls of one round each, which compile the round
and whose states the float64 reference (``reference/fedlt_ref.py``)
follows after the window.  The window is one more call with the same
experiment, state and engine, cut when its time is up.  Every round the
engine makes is kept, and after the window each is checked against the
deployment's own geometry and scheduling rule
(``reference/deliveries.py``): the participants, the deliveries and the
round times.
"""
from __future__ import annotations

import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, generate
from chipbench.reference import deliveries, fedlt_ref

FAULTS = (None, "unchanged", "half_batch", "wrong_participant")


class WindowClosed(Exception):
    """Raised from the engine's call once the window's time is up."""


class EngineClock:
    """The program's engine, with simulated time carried across calls,
    each round's engine call timed, and the window opened and closed at
    round boundaries."""

    def __init__(self, engine, wrong_participant: bool = False):
        self._engine = engine
        self.offset = 0.0
        self.starts = []
        self.spans = []
        self.results = []            # every round the engine made
        self.open_at = None          # (round index, seconds, start callback)
        self.deadline = None
        self.wrong_participant = wrong_participant

    def run_round(self, t, msg):
        t0 = time.perf_counter()
        if self.open_at is not None and len(self.starts) == self.open_at[0]:
            _, seconds, start_window = self.open_at
            t0 = start_window()
            self.deadline = t0 + seconds
            self.open_at = None
        elif self.deadline is not None and t0 >= self.deadline:
            self.deadline = None
            raise WindowClosed
        with jax.profiler.TraceAnnotation("engine.run_round"):
            res = self._engine.run_round(self.offset + t, msg)
        if self.wrong_participant:
            _swap_participant(res)
        self.starts.append(t0)
        self.spans.append(time.perf_counter() - t0)
        self.results.append(res)
        return res

    def __getattr__(self, name):
        return getattr(self._engine, name)


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, log,
                 fault=None):
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.cfg, self.traffic, self.seed, self.log = config, traffic, seed, log
        self.fault = fault
        self.check_rounds = traffic["check_rounds"]
        self.counts = {}

    def _algorithm(self):
        from repro.core.compression import UniformQuantizer
        from repro.core.error_feedback import EFChannel
        from repro.core.fedlt import FedLT
        from repro.data.logistic import make_local_loss
        c = self.cfg
        quant = UniformQuantizer(levels=c["levels"], vmin=c["vmin"],
                                 vmax=c["vmax"], clip=True)
        alg = FedLT(loss=make_local_loss(eps=c["eps"], n_agents=c["n_agents"]),
                    n_epochs=c["n_epochs"], gamma=c["gamma"], rho=c["rho"],
                    uplink=EFChannel(quant, enabled=c["error_feedback"]["uplink"]),
                    downlink=EFChannel(quant,
                                       enabled=c["error_feedback"]["downlink"]),
                    fused_uplink=c["fused_uplink"])
        return alg, quant

    def setup(self) -> None:
        from repro.api import Experiment
        from repro.sim import Engine, get_scenario
        c = self.cfg
        self.key = generate.seed_key(self.seed)
        self.data = generate.logistic_data(
            self.key, n_agents=c["n_agents"], m=c["m"], dim=c["dim"],
            label_noise=c["label_noise"])
        feed = self.data
        if self.fault == "half_batch":
            # half of every agent's rows left out, the mean over the rest
            half = c["m"] // 2
            feed = {"a": self.data["a"][:, :half], "b": self.data["b"][:, :half]}
        self.feed = feed
        alg, quant = self._algorithm()
        scenario = get_scenario(c["scenario"])
        _check_scenario(scenario, c["constellation"])
        engine = Engine(scenario, seed=self.seed % 2 ** 32)
        self.clock = EngineClock(engine,
                                 wrong_participant=self.fault == "wrong_participant")
        self.exp = Experiment(None, alg, engine=self.clock, compressor=quant,
                              mode=c["mode"], measure=c["measure"])
        self.state0 = self.exp.init(jnp.zeros((c["dim"],), jnp.float32),
                                    c["n_agents"])
        # the first rounds one call each, so that the state after each is
        # read; the window's calls go on from the same engine and state
        state = self.state0
        self.prog_states = []
        for k in range(self.check_rounds):
            state = self._call(state, 1, k)
            self.prog_states.append(jax.device_get(state))
        self.first = list(self.clock.results)
        self.state = state
        self.calls = self.check_rounds
        del self.state0
        self.log(f"{c['scenario']}: {self.check_rounds} set-up rounds, "
                 f"participants {[int(r.mask.sum()) for r in self.first]}")

    def _call(self, state, n_rounds: int, call: int):
        if self.fault == "unchanged":
            self.exp.run(state, self.feed, n_rounds,
                         jax.random.fold_in(self.key, call))
            out, t_end = state, self._last_t
        else:
            res = self.exp.run(state, self.feed, n_rounds,
                               jax.random.fold_in(self.key, call))
            out, t_end = res.state, res.logs[-1].time
        self._last_t = t_end
        self.clock.offset += t_end
        return out

    _last_t = 0.0

    # -- the window --------------------------------------------------------
    def window(self, seconds: float, start_window):
        """One call of ``Experiment.run`` that goes on until the window's
        time is up.  Its first round traces the call's freshly jitted
        round, so the window opens at its second round's engine call; it
        closes at the first engine call after ``seconds``, and then the
        device is waited for.  A round's wall time runs from its engine
        call to the next round's (the last one's: to the end)."""
        clock = self.clock
        first = len(clock.starts) + 1
        clock.open_at = (first, seconds, start_window)
        try:
            self._call(self.state, self.traffic["max_rounds"], self.calls)
        except WindowClosed:
            pass
        else:
            raise RuntimeError(f"{self.traffic['max_rounds']} rounds ran out "
                               f"before the window closed")
        for a in jax.live_arrays():
            a.block_until_ready()
        end = time.perf_counter()
        starts = clock.starts[first:] + [end]
        times = [b - a for a, b in zip(starts[:-1], starts[1:])]
        self.spans = {"engine.run_round": clock.spans[first:]}
        return times, end - starts[0]

    def hlo_texts(self) -> list:
        """The round is jitted inside ``Experiment.run``: its events keep
        their instruction names, and no metric of this path reads a
        scope."""
        return []

    def failures(self) -> int:
        """A round of this path has no outcome of its own that could
        fail; the comparison of the set-up rounds decides ``correct``."""
        return 0

    def memory_bytes(self) -> int:
        stats = jax.devices()[0].memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        self.log(f"peak_bytes_in_use {peak}")
        return peak

    def release(self) -> None:
        self.rounds = self.clock.results
        del self.state, self.exp, self.clock

    # -- the comparison ----------------------------------------------------
    def reference(self, control: bool = False) -> list:
        """The reference's state after each set-up round, given the
        participants the engine chose (``control``: computed in
        bfloat16)."""
        ref = fedlt_ref.Round(self.cfg, jax.device_get(self.data), low=control)
        st = ref.start(np.zeros(self.cfg["dim"]))
        out = []
        for res in self.first:
            if res.mask.sum() == 0:
                raise RuntimeError("a set-up round had no participant")
            st = ref.step(st, res.mask)
            out.append(types.SimpleNamespace(**st))
        return out

    def compare(self) -> dict:
        return self.numbers(self.reference())

    def schedule_check(self) -> dict:
        """Every round the engine made, against the deployment's geometry
        and scheduling rule (once; the control shares the program's
        engine)."""
        if not hasattr(self, "_schedule"):
            t0 = time.perf_counter()
            found = deliveries.Checker(self.cfg["constellation"]).check(self.rounds)
            for note in found.pop("notes"):
                self.log(f"schedule: {note}")
            self.log(f"schedule: {found['rounds']} rounds checked in "
                     f"{time.perf_counter() - t0:.3f} s")
            self._schedule = found
        return self._schedule

    def numbers(self, ref: list, prog: list = None) -> dict:
        """The program's states after the set-up rounds (or the control's)
        against the reference's, and the engine's rounds against the
        deployment's rule.  An agent is a leaf here: its row of each
        array.  The loss is the objective at the agents' mean model,
        evaluated alike for both sides."""
        sched = self.schedule_check()
        prog = self.prog_states if prog is None else prog
        f = fedlt_ref.Round(self.cfg, jax.device_get(self.data))
        loss_gap = max(abs(f.loss(p.x) - f.loss(r.x)) / abs(f.loss(r.x))
                       for p, r in zip(prog, ref))
        keep = compare.kept_leaves(_rows(ref[0].x))
        return {
            "loss_gap": loss_gap,
            "dx1_gap": compare.worst_gap(_rows(prog[0].x), _rows(ref[0].x), keep),
            "dx3_gap": compare.worst_gap(_rows(prog[-1].x), _rows(ref[-1].x), keep),
            # logged, not judged: a wire entry at a rounding tie of the
            # quantizer takes the next level in float32, not in float64
            "zhat3_gap": compare.worst_gap(_rows(prog[-1].z_hat), _rows(ref[-1].z_hat)),
            "cup3_gap": compare.worst_gap(_rows(prog[-1].c_up), _rows(ref[-1].c_up)),
            "rule_faults": sched["rule_faults"],
            "delivery_faults": sched["delivery_faults"],
        }


def _check_scenario(scenario, stated: dict) -> None:
    """The program's scenario has to be the deployment the configuration
    states, or the check of its rounds would judge another one."""
    w, gs, link = scenario.walker, scenario.stations, scenario.link
    run = {"n_sats": w.n_sats, "n_planes": w.n_planes, "altitude_m": w.altitude,
           "inclination_deg": w.inclination, "phasing": w.phasing,
           "stations": [(g.lat, g.lon, g.mask_angle) for g in gs],
           "plan_dt_s": scenario.dt, "compute_s": scenario.compute_time,
           "k_direct": scenario.k_direct, "n_relay": scenario.n_relay,
           "max_hops": scenario.max_hops, "lookahead_s": scenario.lookahead,
           "link": (link.gs_rate, link.gs_latency, link.isl_rate,
                    link.isl_latency),
           "extras": (scenario.dropout, scenario.channel, scenario.topology,
                      scenario.faults)}
    s, g, ln = stated["walker"], stated["station"], stated["link"]
    want = dict(s, stations=[(g["lat_deg"], g["lon_deg"], g["mask_deg"])],
                plan_dt_s=stated["plan_dt_s"], compute_s=stated["compute_s"],
                k_direct=stated["k_direct"], n_relay=stated["n_relay"],
                max_hops=stated["max_hops"], lookahead_s=stated["lookahead_s"],
                link=(ln["gs_rate_bytes_s"], ln["gs_latency_s"],
                      ln["isl_rate_bytes_s"], ln["isl_latency_s"]),
                extras=(0.0, None, None, None))
    if run != want:
        raise RuntimeError(f"the program's scenario {run} is not the "
                           f"configuration's {want}")


def _swap_participant(res) -> None:
    """The fault ``wrong_participant``: the round's last delivery is
    credited to the lowest-numbered satellite that took no part."""
    if not res.deliveries:
        return
    d = res.deliveries[-1]
    other = int(np.flatnonzero(~res.mask)[0])
    res.mask[d.sat] = False
    res.mask[other] = True
    if d.gateway == d.sat:
        d.gateway = other
    d.sat = other


def _rows(x) -> np.ndarray:
    """Norm of each agent's row."""
    return np.linalg.norm(np.asarray(x, np.float64), axis=1)
