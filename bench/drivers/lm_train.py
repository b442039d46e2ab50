"""Regime B: `launch.train`'s resident rounds, for a fixed time.

The round is `launch.steps.build_train_algo` -> `DFedPGP.round_fn_flat`,
jitted as `launch.train.main` jits it: the state is the donated carry, and
on a mesh (one client per chip) it is built in place and kept there by
`out_shardings`.  Each window call draws the round's token batches with the
benchmark's jitted copy of `synth_lm_batch`, takes `schedule.at(r)`, runs
the round and fetches its metrics to the host, as `main` does every round.
The weights are the benchmark's own, made from the run's seed in the same
jitted call that packs them into the resident buffer.
"""
from __future__ import annotations

import functools

import jax
import numpy as np

from bench import counts, generators, seeds
from bench.drivers import _common
from bench.reference import dfedpgp as ref_round

span = jax.profiler.TraceAnnotation


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        arch_id=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        qkv_bias=cfg["qkv_bias"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"])


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int, devices: list):
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.launch import steps
        from repro.launch.mesh import make_host_mesh
        from repro.spec import make_algo_spec

        self.cfg, self.tr, self.seed = cfg, traffic, seed
        self.devices = devices
        self.m = m = traffic["clients"]
        self.topo_seed = seeds.topology_seed(seed)
        self.weights_key = seeds.stream(seed, "weights")
        self.run_key = seeds.stream(seed, "rounds")
        mcfg = model_config(cfg)
        if len(devices) == 1:
            self.mesh = None
        elif len(devices) == m:
            self.mesh = make_host_mesh(m, 1)
        else:
            raise ValueError(f"{m} clients on {len(devices)} chips: one chip "
                             f"holds them all, or each holds one")
        spec = make_algo_spec(
            "dfedpgp", topology=traffic["topology"],
            n_neighbors=traffic.get("n_neighbors", 2), seed=self.topo_seed,
            gossip=traffic["mix"], resident=True)
        self.schedule = spec.schedule(m)
        layout = steps.Layout(("data",), (), ("model",), (), m,
                              traffic["batch"])
        algo, _, _, flat_layout = steps.build_train_algo(
            mcfg, self.mesh, layout, k_u=traffic["k_u"], k_v=traffic["k_v"],
            spec=spec, lr=traffic["lr"])
        opt = algo.opt_u
        stated = (traffic["lr"], traffic["momentum"], traffic["weight_decay"],
                  traffic["lr_decay"])
        if (opt.lr, opt.momentum, opt.weight_decay, algo.lr_decay) != stated:
            raise ValueError(f"the program's SGD {opt} and decay "
                             f"{algo.lr_decay} differ from the traffic's "
                             f"{stated}")
        init_fn = _common.stacked_init(cfg, m)

        def init_state(key):
            return algo.init_flat(init_fn(key), flat_layout)[0]

        jit_kw: dict = dict(donate_argnums=(0,))
        batch_sh = None
        if self.mesh is None:
            self.state = jax.jit(init_state)(self.weights_key)
        else:
            struct = jax.eval_shape(init_state, self.weights_key)
            st_sh = steps.flat_state_shardings(struct, self.mesh, layout)
            self.state = jax.jit(init_state, out_shardings=st_sh)(
                self.weights_key)
            jit_kw["out_shardings"] = (st_sh,
                                       NamedSharding(self.mesh,
                                                     PartitionSpec()))
            b_struct = jax.eval_shape(self._batches, 0)
            batch_sh = steps.batch_specs(b_struct, self.mesh, layout,
                                         n_lead=2)

        @functools.partial(jax.jit, **jit_kw)
        def round_fn(state, P, batches):
            return algo.round_fn_flat(state, P, batches, flat_layout)

        self.round_fn = round_fn
        self.gen = jax.jit(self._batches, out_shardings=batch_sh)
        self.readings = _common.Readings(cfg, m)
        if self.readings.d_flat != flat_layout.d_flat:
            raise ValueError(f"the program's buffer is {flat_layout.d_flat} "
                             f"wide; the configuration's shared part is "
                             f"{self.readings.d_flat}")
        self.r = 0
        self.losses: list = []
        self.last_args = None

    def _batches(self, r):
        tr = self.tr
        return generators.lm_round_batches(
            self.run_key, r, self.cfg["vocab_size"], self.m, tr["k_v"],
            tr["k_u"], tr["batch"], tr["seq"])

    def _ctx(self):
        import contextlib
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    # -- the window's call ---------------------------------------------------
    def step(self):
        r = self.r
        with self._ctx():
            with span("bench.batches"):
                batches = self.gen(r)
            with span("bench.schedule"):
                P = self.schedule.at(r)
            with span("bench.dispatch"):
                self.state, metrics = self.round_fn(self.state, P, batches)
            self.last_args = (P, batches)
            with span("bench.fetch"):
                metrics = jax.device_get(metrics)
        self.losses.append(metrics["loss_u"])
        self.r += 1
        return metrics

    def drain(self):
        jax.block_until_ready(self.state)

    # -- set-up -----------------------------------------------------------
    def setup(self, warm_rounds: int = 1):
        for r in range(ref_round.ROUNDS):
            metrics = self.step()
            with self._ctx():
                self.readings.after_round(r, self.state, metrics,
                                          self.weights_key)
        for _ in range(warm_rounds):
            self.step()
        self.drain()
        self.losses.clear()

    def lowered(self):
        P, batches = self.last_args
        with self._ctx():
            return self.round_fn.lower(self.state, P, batches)

    # -- accounting -------------------------------------------------------
    def work(self, rounds: int) -> dict:
        return {"rounds": rounds,
                "tokens": rounds * counts.round_tokens(self.tr)}

    def round_ops(self) -> float:
        return counts.round_ops(self.cfg, self.tr)

    def failed(self) -> int:
        return int(np.sum(~np.isfinite(np.asarray(self.losses, np.float64))))

    def free(self):
        del self.state, self.round_fn, self.gen
        self.last_args = None

    # -- the reference ------------------------------------------------------
    def reference(self, precision: str = "reference", fault: str = "none"):
        cfg, tr, m = self.cfg, self.tr, self.m
        gen = jax.jit(self._batches)

        def topology(r):
            return generators.dense(*generators.topology(
                tr["topology"], m, tr.get("n_neighbors", 2), self.topo_seed,
                r))

        hp = {"lr": tr["lr"], "momentum": tr["momentum"],
              "weight_decay": tr["weight_decay"],
              "lr_decay": tr["lr_decay"], "k_u": tr["k_u"],
              "k_v": tr["k_v"]}
        return _common.reference_readings(
            cfg, hp, m, tr["reference_client_chunk"],
            _common.make_init_stacked(cfg, self.weights_key, m), gen,
            topology, self.devices, precision=precision, fault=fault)
