"""Path driver: the deploy round, ``DeployFedLT.round_step``, jitted with
its state donated, on a dense decoder at published widths.

Set-up makes the weights and every batch on the device from the seed,
compiles the round for the cell's shapes only, and drives that compiled
round through its first two rounds on batches that all differ.  The
program's losses and leaf norms after rounds 1 and 2 are kept; the window
then goes on with the same compiled round and state.  After the window
the state is freed and the float32 reference (``reference/lm_ref.py``)
follows the same two rounds from the same weights and batches.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, counts, generate
from chipbench.reference import lm_ref

#: rounds the reference follows: two, so that it stays near the window's
#: length (it keeps one agent's state on the chip at a time, and the rest
#: cross to host memory and back)
CHECK_ROUNDS = 2
FAULTS = (None, "unchanged", "half_batch")


def model_config(cfg: dict):
    """The program's model description for a configuration file."""
    from repro.models.config import ModelConfig
    d = cfg["hidden_size"]
    return ModelConfig(
        name=cfg["name"], arch_type="dense",
        n_layers=cfg["num_hidden_layers"], d_model=d,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rotary_pct=1.0, rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], mlp_gated=True,
        mlp_act=cfg["hidden_act"], tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"], max_seq=cfg["max_position_embeddings"])


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, log,
                 fault=None):
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.cfg, self.traffic, self.seed, self.log = config, traffic, seed, log
        self.fault = fault
        t = traffic
        self.agents, self.batch, self.seq = t["agents"], t["batch"], t["seq"]
        self.n_epochs = t["n_epochs"]
        self.pool = max(t["pool_rounds"], CHECK_ROUNDS)
        self.counts = {
            "round_flops": counts.round_train_flops(
                config, self.agents, self.batch, self.seq, self.n_epochs),
            "quant_pipeline_bytes": counts.quant_pipeline_bytes(
                counts.dense_leaf_sizes(config, self.agents),
                jnp.dtype(config["torch_dtype"]).itemsize,
                config["algorithm"]["levels"]),
            "tokens_per_round": self.agents * self.batch * self.seq,
        }

    # -- set-up ------------------------------------------------------------
    def _labels(self, tokens):
        if self.fault != "half_batch":
            return tokens
        # half of every agent's tokens left out: the loss is the mean
        # over the rest
        return tokens.at[..., self.seq // 2:].set(-1)

    def setup(self) -> None:
        from repro.core.deploy import DeployFedLT, DeployState
        cfg = self.cfg
        a = cfg["algorithm"]
        self.alg = DeployFedLT(
            cfg=model_config(cfg), n_epochs=self.n_epochs, gamma=a["gamma"],
            rho=a["rho"], levels=a["levels"], vmin=a["vmin"], vmax=a["vmax"],
            compress=True, pack_wire=a["pack_wire"],
            fuse_pipeline=a["fuse_pipeline"], backend=a["backend"])
        n = self.agents

        def init(key):
            p0 = generate.dense_weights(cfg, key)
            stack = lambda t: jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), t)
            zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
            return p0, DeployState(x=stack(p0), z=stack(p0), c_up=zeros(stack(p0)),
                                   y_hat=p0, c_down=zeros(p0),
                                   k=jnp.zeros((), jnp.int32))

        t0 = time.perf_counter()
        key = generate.seed_key(self.seed)
        p0, state = jax.block_until_ready(jax.jit(init)(key))
        tokens = generate.token_pool(key, rounds=self.pool, agents=n,
                                     batch=self.batch, seq=self.seq,
                                     vocab=cfg["vocab_size"])
        self.batches = [{"tokens": tokens[r], "labels": self._labels(tokens[r])}
                        for r in range(self.pool)]
        jax.block_until_ready(self.batches)
        t1 = time.perf_counter()
        self.step = jax.jit(self.alg.round_step, donate_argnums=0).lower(
            state, self.batches[0]).compile()
        t2 = time.perf_counter()
        self.hlo = [self.step.as_text()]
        mem = self.step.memory_analysis()
        self.round_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        self.log(f"compiled round {self.round_bytes} bytes (arguments "
                 f"{mem.argument_size_in_bytes}, temporaries "
                 f"{mem.temp_size_in_bytes}, aliased {mem.alias_size_in_bytes})")
        self.n_params = sum(x.size for x in jax.tree_util.tree_leaves(p0))
        self.log(f"params per copy {self.n_params}; tokens per round "
                 f"{self.counts['tokens_per_round']}; local-training FLOP per "
                 f"round {self.counts['round_flops']:.6e}")

        # the program's readings, laid out as the reference's: agent-major
        self.prog = {"loss": []}
        stacked = lambda t, base=None: _agent_major(
            compare.device_norms_stacked(t, base))
        for k in range(CHECK_ROUNDS):
            t_round = time.perf_counter()
            state, metrics = self._call(state, self.batches[k])
            jax.block_until_ready((state, metrics))
            # the last set-up round's wall time sizes the window's
            # dispatch-ahead
            self.round_est = time.perf_counter() - t_round
            self.prog["loss"].append(float(metrics["loss"]))
            if k == 0:
                self.prog["dx1"] = stacked(state.x, p0)
            if k == CHECK_ROUNDS - 1:
                self.prog["dx2"] = stacked(state.x, p0)
                self.prog["cup2"] = stacked(state.c_up)
                self.prog["yhat2"] = _flat(compare.device_norms(state.y_hat, p0))
                self.prog["cdown2"] = _flat(compare.device_norms(state.c_down))
        self.log(f"program loss, rounds 1-{CHECK_ROUNDS}: {self.prog['loss']}")
        self.log(f"set-up phases: weights and batches {t1 - t0:.3f} s, "
                 f"compile {t2 - t1:.3f} s, first rounds "
                 f"{time.perf_counter() - t2:.3f} s")
        del p0
        self.state = state
        self.k = CHECK_ROUNDS

    def _call(self, state, batch):
        if self.fault == "unchanged":
            _, metrics = self.step(jax.tree_util.tree_map(jnp.copy, state), batch)
            return state, metrics
        return self.step(state, batch)

    # -- the window --------------------------------------------------------
    def window(self, seconds: float, start_window):
        """Rounds back to back, dispatched up to ``ahead_seconds`` of
        rounds ahead of the one waited for, so that the chip stays fed
        while the host stalls.  Nothing more is sent once the rounds in
        flight would end past ``seconds`` (by the set-up's round time);
        the window closes when every round sent has finished, and all of
        them count.  A round's wall time runs from the previous round's
        end (the first: from the window's start) to its own, which is
        when its outputs are on the device."""
        ahead = max(1, round(self.traffic["ahead_seconds"] / self.round_est))
        self.log(f"dispatching up to {ahead} rounds ahead")
        times = []
        self.losses = []
        sent = []
        sending = True
        start = last = start_window()
        while True:
            sending = sending and (time.perf_counter() - start
                                   + len(sent) * self.round_est < seconds)
            if sending:
                with jax.profiler.TraceAnnotation("chipbench.round"):
                    self.state, metrics = self._call(
                        self.state, self.batches[self.k % self.pool])
                self.losses.append(metrics["loss"])
                sent.append(metrics)
                self.k += 1
                if len(sent) < ahead:
                    continue
            elif not sent:
                return times, last - start
            # the state went on into the next round; its metrics are
            # outputs of the same run and are ready with it
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                jax.block_until_ready(sent.pop(0))
            t = time.perf_counter()
            times.append(t - last)
            last = t

    def hlo_texts(self) -> list:
        """The compiled programs of the window, for naming trace events."""
        return self.hlo

    def failures(self) -> int:
        """Rounds of the window whose loss is not finite."""
        losses = np.asarray(jax.device_get(self.losses), np.float64)
        return int(np.sum(~np.isfinite(losses)))

    def memory_bytes(self) -> int:
        stats = jax.devices()[0].memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        self.log(f"peak_bytes_in_use {peak}; compiled round {self.round_bytes}")
        return max(peak, int(self.round_bytes))

    def release(self) -> None:
        del self.state, self.step
        self.batches = [jax.device_get(b) for b in self.batches[:CHECK_ROUNDS]]

    # -- the comparison ----------------------------------------------------
    def reference(self, control: bool = False, precision: str = None) -> dict:
        """Norms and losses of the reference (``control``: computed in
        float8; ``precision`` names a witness of ``lm_ref.PRECISIONS``)."""
        cfg = self.cfg
        precision = precision or ("fp8" if control else "f32")
        ref = lm_ref.Round(cfg, cfg["algorithm"], self.n_epochs, precision)
        p0 = jax.jit(lambda k: generate.dense_weights(cfg, k))(
            generate.seed_key(self.seed))
        tokens = jnp.stack([jnp.asarray(b["tokens"]) for b in self.batches])
        labels = tokens          # the reference always sees the whole batch
        n = self.agents
        seen = {"loss": [], "dx": {}, "cup": {}, "yhat": {}, "cdown": {}}

        def observe(r, agent, tree, cache, loss=None):
            if agent is None:
                seen["yhat"][r] = _flat(compare.device_norms(tree, p0))
                seen["cdown"][r] = _flat(compare.device_norms(cache))
                seen["loss"].append(loss)
            else:
                seen["dx"][r, agent] = _flat(compare.device_norms(tree, p0))
                seen["cup"][r, agent] = _flat(compare.device_norms(cache))

        ref.run(p0, tokens, labels, CHECK_ROUNDS, observe)
        agents = lambda key, r: np.concatenate([seen[key][r, a] for a in range(n)])
        last = CHECK_ROUNDS
        return {"loss": seen["loss"], "dx1": agents("dx", 1),
                "dx2": agents("dx", last), "cup2": agents("cup", last),
                "yhat2": seen["yhat"][last], "cdown2": seen["cdown"][last]}

    def compare(self) -> dict:
        return self.numbers(self.reference())

    def numbers(self, ref: dict, prog: dict = None) -> dict:
        """The compared numbers of ``prog`` (default: this run's program;
        the control passes the low-precision reference's readings) against
        the reference's readings ``ref``."""
        prog = self.prog if prog is None else prog
        keep = compare.kept_leaves(ref["dx1"])
        loss_gap = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"]))
        return {
            "loss_gap": loss_gap,
            "dx1_gap": compare.worst_gap(prog["dx1"], ref["dx1"], keep),
            "dx2_gap": compare.worst_gap(prog["dx2"], ref["dx2"], keep),
            "cup2_gap": compare.worst_gap(prog["cup2"], ref["cup2"]),
            "yhat2_gap": compare.worst_gap(prog["yhat2"], ref["yhat2"]),
            "cdown2_gap": compare.worst_gap(prog["cdown2"], ref["cdown2"]),
        }


def _flat(norms) -> np.ndarray:
    """Device norms (a list of scalars or of ``(A,)``) → one float array,
    leaf-major."""
    return np.concatenate([np.atleast_1d(np.asarray(v, np.float64))
                           for v in jax.device_get(norms)])


def _agent_major(per_leaf) -> np.ndarray:
    """Per-leaf ``(A,)`` norms → one float array, agent-major."""
    return np.stack([np.asarray(v, np.float64)
                     for v in jax.device_get(per_leaf)]).T.ravel()
