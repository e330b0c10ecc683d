"""Plain float64 reference of the paper's Fed-LT round (Algorithm 2) on
regularized logistic regression, in NumPy.

    f_i(x) = mean_h log(1 + exp(−b_h·a_hᵀx)) + ε/(2N)·‖x‖²

One round, given the set of agents that take part:

    ȳ   = mean_i ẑ_i;  m = ȳ + c;  y = Q(m);  c ← m − y     (downlink EF)
    v_i = 2y − z_i;  w ← N_e steps of w − γ(∇f_i(w) + (w − v_i)/ρ)
    z_i ← z_i + 2(w − y);  x_i ← w                          (taking part)
    m_i = z_i + c_i;  ẑ_i ← Q(m_i);  c_i ← m_i − ẑ_i         (taking part)

``Q`` is the uniform quantizer with ``levels`` steps over [vmin, vmax],
clipping.  ``low=True`` is the control: the same round with every array
and every intermediate result, the quantizer's too, rounded to bfloat16.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np


def _round_bf16(x):
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


class Round:
    def __init__(self, cfg: dict, data: dict, low: bool = False):
        self.cfg = cfg
        self.r = _round_bf16 if low else (lambda x: x)
        dt = np.float32 if low else np.float64
        self.a = self.r(np.asarray(data["a"], dt))
        self.b = self.r(np.asarray(data["b"], dt))
        self.dt = dt

    def quantize(self, m):
        r, c = self.r, self.cfg
        delta = (c["vmax"] - c["vmin"]) / c["levels"]
        idx = np.floor(r(r(r(np.clip(m, c["vmin"], c["vmax"]) - c["vmin"]) / delta) + 0.5))
        return r(r(np.clip(idx, 0, c["levels"]) * delta) + c["vmin"])

    def grad(self, w):
        """∇f_i at each agent's ``w`` (N, d)."""
        r, c = self.r, self.cfg
        margins = r(self.b * r(np.einsum("imd,id->im", self.a, w)))
        s = r(1.0 / (1.0 + np.exp(margins)))              # σ(−margin)
        g = r(-np.einsum("im,imd->id", r(self.b * s), self.a) / self.a.shape[1])
        return r(g + r(c["eps"] / c["n_agents"] * w))

    def loss(self, x):
        """Σ_i f_i at the agents' mean model, in float64."""
        c = self.cfg
        xm = np.mean(np.asarray(x, np.float64), axis=0)
        a = np.asarray(self.a, np.float64)
        b = np.asarray(self.b, np.float64)
        margins = b * np.einsum("imd,d->im", a, xm)
        per = np.mean(np.logaddexp(0.0, -margins), axis=1)
        return float(np.sum(per + c["eps"] / (2 * c["n_agents"]) * xm @ xm))

    def step(self, st: dict, active) -> dict:
        r, c = self.r, self.cfg
        act = np.asarray(active, bool)[:, None]
        m = r(np.mean(st["z_hat"], axis=0) + st["c_down"])
        y = self.quantize(m)
        c_down = r(m - y)
        v = r(2.0 * y[None] - st["z"])
        w = st["x"]
        for _ in range(c["n_epochs"]):
            w = r(w - c["gamma"] * r(self.grad(w) + r((w - v) / c["rho"])))
        z_new = r(st["z"] + r(2.0 * r(w - y[None])))
        x = np.where(act, w, st["x"])
        z = np.where(act, z_new, st["z"])
        mu = r(z + st["c_up"])
        wire = self.quantize(mu)
        return {"x": x, "z": z,
                "c_up": np.where(act, r(mu - wire), st["c_up"]),
                "z_hat": np.where(act, wire, st["z_hat"]),
                "c_down": c_down}

    def start(self, x0) -> dict:
        n = self.cfg["n_agents"]
        x = np.broadcast_to(np.asarray(x0, self.dt), (n, len(x0))).copy()
        return {"x": x, "z": x.copy(), "c_up": np.zeros_like(x),
                "z_hat": x.copy(), "c_down": np.zeros(len(x0), self.dt)}
