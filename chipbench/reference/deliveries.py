"""Plain check of the constellation's deliveries, from the benchmark's own
orbit geometry.

The deployment is stated in the configuration file (``deployment``): a
Walker-delta shell of circular orbits over a spherical Earth, one ground
station that sees a satellite above a mask angle, a contact plan sampled
on a fixed grid, the round's scheduling rule and the link rates.  This
module follows that statement and nothing of the program's code.

The contact plan: a satellite is in contact at time ``t`` when it is above
the mask at the grid time ``dt·floor(t/dt)``; a window rises at the first
grid time of a run of such times.  A synchronous round that starts at
``t0`` has every satellite ready at ``t0 + compute_s``; its gateways are the
``k_direct`` satellites whose contact starts soonest after that (a tie goes
to the lower index), within ``lookahead_s`` of ``t0``.  Its relays are the
other satellites within ``max_hops`` inter-satellite hops of a gateway
(in-plane ring neighbours and the same slot in the adjacent planes), each
routed to a nearest gateway; a gateway takes at most ``n_relay`` of them,
the nearest first and then the lower index.  Which of two equally near
gateways takes a relay the rule leaves open, so the check accepts either.

What one round must show:

- its gateways (the deliveries with no hop) are exactly the rule's;
- each relay is routed to a gateway at its nearest distance, within
  ``max_hops``, and no gateway has more than ``n_relay``; a gateway with
  room, or with a relay later in the order, has left out no satellite
  that only it is nearest to;
- the mask of satellites that took part is the set of deliveries;
- each delivery's gateway sees the station over its transmission, from the
  start of the window it names, which is the round's ready time or a true
  rise of the gateway's contact; it lands no sooner
  than training, the hops and the transmission allow; transmissions at
  the station do not overlap;
- the next round starts when this one's last delivery lands.
"""
from __future__ import annotations

import numpy as np

R_EARTH_M = 6371.0e3            # spherical Earth
MU_M3_S2 = 3.986004418e14       # Earth's gravitational parameter
OMEGA_EARTH_RAD_S = 7.2921159e-5
#: seconds of float slack in the comparisons of times
TIME_TOL = 1e-6


class Constellation:
    def __init__(self, dep: dict):
        w, gs = dep["walker"], dep["station"]
        self.n = int(w["n_sats"])
        self.planes = int(w["n_planes"])
        self.spp = self.n // self.planes
        radius = R_EARTH_M + float(w["altitude_m"])
        self.radius = radius
        self.mean_motion = np.sqrt(MU_M3_S2 / radius ** 3)
        inc = np.deg2rad(float(w["inclination_deg"]))
        sat = np.arange(self.n)
        plane, slot = sat // self.spp, sat % self.spp
        raan = 2.0 * np.pi * plane / self.planes
        self.phase0 = (2.0 * np.pi * slot / self.spp
                       + 2.0 * np.pi * int(w["phasing"]) * plane / self.n)
        # each orbit's plane: the ascending node's direction, and the
        # direction a quarter orbit further on
        self.node = np.stack([np.cos(raan), np.sin(raan), np.zeros(self.n)], 1)
        self.quarter = np.stack([-np.sin(raan) * np.cos(inc),
                                 np.cos(raan) * np.cos(inc),
                                 np.full(self.n, np.sin(inc))], 1)
        self.lat = np.deg2rad(float(gs["lat_deg"]))
        self.lon = np.deg2rad(float(gs["lon_deg"]))
        self.mask_deg = float(gs["mask_deg"])
        self.dt = float(dep["plan_dt_s"])

    def elevation_deg(self, t, sats) -> np.ndarray:
        """Elevation of satellite ``sats[i]`` above the station's horizon at
        ``t[i]`` (arrays of one shape)."""
        t = np.asarray(t, np.float64)
        sats = np.asarray(sats, np.int64)
        u = self.phase0[sats] + self.mean_motion * t
        pos = self.radius * (np.cos(u)[..., None] * self.node[sats]
                             + np.sin(u)[..., None] * self.quarter[sats])
        lon = self.lon + OMEGA_EARTH_RAD_S * t
        up = np.stack([np.cos(self.lat) * np.cos(lon),
                       np.cos(self.lat) * np.sin(lon),
                       np.full(np.shape(lon), np.sin(self.lat))], -1)
        rel = pos - R_EARTH_M * up
        sin_el = np.sum(rel * up, -1) / np.linalg.norm(rel, axis=-1)
        return np.rad2deg(np.arcsin(np.clip(sin_el, -1.0, 1.0)))

    def in_view(self, grid_idx, sats) -> np.ndarray:
        """Above the mask at grid times ``grid_idx·dt``."""
        t = np.asarray(grid_idx, np.float64) * self.dt
        return self.elevation_deg(t, sats) > self.mask_deg

    def grid_index(self, t) -> np.ndarray:
        """The grid time at or before ``t``, as an index."""
        t = np.asarray(t, np.float64)
        k = np.floor(t / self.dt)
        return (k - (k * self.dt > t)).astype(np.int64)

    def view_table(self, k0: int, k1: int) -> np.ndarray:
        """``(k1 − k0, n)`` bool: every satellite in view at grid times
        ``k0 … k1 − 1``."""
        out = np.empty((k1 - k0, self.n), bool)
        sats = np.arange(self.n)
        for a in range(k0, k1, 2048):
            b = min(a + 2048, k1)
            idx = np.arange(a, b)[:, None]
            out[a - k0:b - k0] = self.in_view(
                np.broadcast_to(idx, (b - a, self.n)),
                np.broadcast_to(sats, (b - a, self.n)))
        return out

    # -- the inter-satellite links --------------------------------------
    def neighbours(self, s: int) -> set:
        plane, slot = divmod(s, self.spp)
        out = {plane * self.spp + (slot - 1) % self.spp,
               plane * self.spp + (slot + 1) % self.spp}
        if self.planes > 1:
            out.add(((plane - 1) % self.planes) * self.spp + slot)
            out.add(((plane + 1) % self.planes) * self.spp + slot)
        out.discard(s)
        return out

    def hops_from(self, src: int, limit: int) -> dict:
        """ISL hop distance from ``src`` to every satellite within
        ``limit`` hops."""
        dist = {src: 0}
        frontier = [src]
        for h in range(1, limit + 1):
            nxt = []
            for s in frontier:
                for nb in self.neighbours(s):
                    if nb not in dist:
                        dist[nb] = h
                        nxt.append(nb)
            frontier = nxt
        return dist


class Checker:
    """Checks recorded rounds against the deployment's rule."""

    def __init__(self, dep: dict):
        self.c = Constellation(dep)
        self.compute_s = float(dep["compute_s"])
        self.k_direct = int(dep["k_direct"])
        self.n_relay = int(dep["n_relay"])
        self.max_hops = int(dep["max_hops"])
        self.lookahead_s = float(dep["lookahead_s"])
        link = dep["link"]
        self.gs_rate = float(link["gs_rate_bytes_s"])
        self.gs_latency = float(link["gs_latency_s"])
        self.isl_rate = float(link["isl_rate_bytes_s"])
        self.isl_latency = float(link["isl_latency_s"])
        self._hops = {}

    def hops(self, s: int) -> dict:
        if s not in self._hops:
            self._hops[s] = self.c.hops_from(s, self.max_hops)
        return self._hops[s]

    def gs_time(self, nbytes: float) -> float:
        return self.gs_latency + nbytes / self.gs_rate

    def isl_time(self, nbytes: float, hops: int) -> float:
        return hops * (self.isl_latency + nbytes / self.isl_rate)

    # -- the rule -------------------------------------------------------
    def gateways(self, t0: float, table: np.ndarray, k0: int) -> list:
        """The round's gateways, soonest contact first."""
        c = self.c
        t_ready = t0 + self.compute_s
        k = int(c.grid_index(t_ready)) - k0
        horizon = int(c.grid_index(t0 + self.lookahead_s)) - k0
        rows = table[k:horizon + 1]
        start = np.full(c.n, np.inf)
        now = rows[0]
        start[now] = t_ready
        later = rows[1:]
        seen = later.any(0) & ~now
        first = np.argmax(later, 0)
        start[seen] = (first[seen] + k + 1 + k0) * c.dt
        ok = np.isfinite(start) & (start <= t0 + self.lookahead_s)
        cand = np.nonzero(ok)[0]
        order = cand[np.argsort(start[cand], kind="stable")]
        return [int(s) for s in order[:self.k_direct]]

    def rule_faults(self, gws: list, deliveries, mask) -> list:
        """What in one round's participants breaks the rule."""
        out = []
        prog_gw = sorted(d.sat for d in deliveries if d.hops == 0)
        if prog_gw != sorted(gws):
            out.append(f"gateways {prog_gw}, the rule's {sorted(gws)}")
        gset = set(gws)
        dist = {}
        for g in gws:
            for s, h in self.hops(g).items():
                dist.setdefault(s, {})[g] = h
        nearest = {s: min(hs.values()) for s, hs in dist.items()}
        taken = {g: [] for g in gws}
        for d in deliveries:
            if d.hops == 0:
                if d.gateway != d.sat:
                    out.append(f"gateway {d.sat} delivered through {d.gateway}")
                continue
            g, s = d.gateway, d.sat
            if g not in gset or s in gset:
                out.append(f"relay {s} through {g}, not a gateway's relay")
                continue
            h = dist.get(s, {}).get(g)
            if h is None or h != d.hops or h != nearest[s]:
                out.append(f"relay {s}: {d.hops} hops to {g}, the nearest "
                           f"gateway {nearest.get(s)} hops")
                continue
            taken[g].append((h, s))
        for g, rel in taken.items():
            if len(rel) > self.n_relay:
                out.append(f"gateway {g} took {len(rel)} relays")
                continue
            last = max(rel) if len(rel) == self.n_relay else None
            mine = {s for _, s in rel}
            for s, hs in dist.items():
                if s in gset or s in mine or hs.get(g) != nearest[s]:
                    continue
                if sum(1 for v in hs.values() if v == nearest[s]) > 1:
                    continue                     # another gateway as near
                if last is None or (nearest[s], s) < last:
                    out.append(f"gateway {g} left out relay {s}")
                    break
        sats = sorted(d.sat for d in deliveries if d.delivered)
        if sats != sorted(int(s) for s in np.nonzero(mask)[0]):
            out.append("the mask is not the set of deliveries")
        return out

    def delivery_faults(self, t0: float, deliveries) -> list:
        """What in one round's deliveries breaks the geometry or the
        timing."""
        c = self.c
        out = []
        if not deliveries:
            return out
        gw = np.array([d.gateway for d in deliveries])
        done = np.array([d.t_done for d in deliveries], np.float64)
        tx = np.array([self.gs_time(d.nbytes) for d in deliveries])
        rise = np.array([d.window for d in deliveries], np.float64)
        ready = t0 + self.compute_s
        k_rise = c.grid_index(rise)
        k_tx = c.grid_index(done - tx)
        k_end = c.grid_index(done - TIME_TOL)
        # every grid time from the window's rise to the transmission's end
        span = np.maximum(k_end - k_rise + 1, 1)
        owner = np.repeat(np.arange(len(deliveries)), span)
        step = np.arange(owner.size) - np.repeat(np.cumsum(span) - span, span)
        seen = c.in_view(k_rise[owner] + step, gw[owner])
        in_view = np.logical_and.reduceat(seen, np.cumsum(span) - span)
        before = np.where(k_rise > 0, c.in_view(k_rise - 1, gw), False)
        for i, d in enumerate(deliveries):
            earliest = (t0 + self.compute_s + self.isl_time(d.nbytes, d.hops)
                        + tx[i])
            if not d.delivered or d.nbytes <= 0 or d.station != 0:
                out.append(f"sat {d.sat}: not delivered to the station")
            elif ((rise[i] != ready and (k_rise[i] * c.dt != rise[i] or before[i]))
                  or k_rise[i] > k_tx[i]):
                out.append(f"sat {d.sat}: window {rise[i]} is neither the "
                           f"ready time nor a rise before its transmission")
            elif not in_view[i]:
                out.append(f"sat {d.sat}: gateway {d.gateway} out of view "
                           f"over its transmission")
            elif d.t_done < earliest - TIME_TOL or d.t_start != t0:
                out.append(f"sat {d.sat}: lands at {d.t_done}, before "
                           f"{earliest}")
        at_station = np.sort(done)
        if np.any(np.diff(at_station) < tx.min() - TIME_TOL):
            out.append("transmissions at the station overlap")
        return out

    def check(self, rounds: list) -> dict:
        """``rounds``: consecutive recorded rounds, each with ``t0``,
        ``duration``, ``mask`` and ``deliveries``.  Returns the numbers
        of rounds whose participants break the rule and of deliveries
        that break the geometry or the timing, and the first few
        findings."""
        c = self.c
        t0s = np.array([r.t0 for r in rounds], np.float64)
        k0 = int(c.grid_index(t0s.min() + self.compute_s))
        k1 = int(c.grid_index(t0s.max() + self.lookahead_s)) + 2
        table = c.view_table(k0, k1)
        bad_rounds = bad_deliveries = 0
        notes = []
        for i, r in enumerate(rounds):
            rule = self.rule_faults(self.gateways(r.t0, table, k0),
                                    r.deliveries, r.mask)
            geo = self.delivery_faults(r.t0, r.deliveries)
            if i + 1 < len(rounds) and r.deliveries:
                last = max(d.t_done for d in r.deliveries)
                if abs(rounds[i + 1].t0 - last) > TIME_TOL:
                    rule.append(f"next round starts at {rounds[i + 1].t0}, "
                                f"the last delivery lands at {last}")
            bad_rounds += bool(rule)
            bad_deliveries += len(geo)
            if (rule or geo) and len(notes) < 5:
                notes.append(f"round at t0={r.t0}: " + "; ".join(rule + geo))
        return {"rounds": len(rounds), "rule_faults": bad_rounds,
                "delivery_faults": bad_deliveries, "notes": notes}
