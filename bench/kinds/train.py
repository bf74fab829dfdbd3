"""Train cells: the program's SCE train step, built and driven as
``launch/train.py`` builds and drives it, at the configuration's matmul
precision (``bench/run.py`` sets it process-wide; ``launch/train.py``
leaves XLA's default, one bfloat16 pass, outside the kernels).

Set-up builds one object — the jitted, donated step of
``launch.steps.make_seqrec_train_step`` with its parameters (made on
the device from the seed by the reference's initializer, in one jitted
call) and AdamW state — and drives it through its first three steps
with the window's own call and feed: a host batch from the program's
``repro.data`` generator at the next cursor step, placed with
``dist.sharding.batch_spec`` on ``launch.mesh.make_host_mesh``, then the
step, then the loss read back. The window goes on with the same object
for ``--seconds``. Afterwards the reference follows the first three
steps and the numbers of ``checks.train_gaps`` are compared.
"""
from __future__ import annotations

import functools
import gc
import time

from harness import cell, checks, seeds

KERNELS = ("mips_topk", "sce_gather_fwd", "sce_gather_bwd")
CHECK_STEPS = 3


class ConfigMismatch(ValueError):
    """The program would not run the configuration as the file states."""


def expected_sce(config, positions: int):
    """n_b, b_x, b_y from the paper's α, β parametrization (§4.2.1)."""
    s = config["sce"]
    n_b = round(s["alpha"] * (positions * s["beta"]) ** 0.5)
    b_x = min(round(s["alpha"] * (positions / s["beta"]) ** 0.5), positions)
    return {"n_b": n_b, "b_x": b_x, "b_y": s["bucket_size_y"]}


def dataset(config, batch: int):
    from repro.data import (LongTailConfig, LongTailDataset, SeqDataConfig,
                            SequenceDataset)

    sizes, data = config["sizes"], config["data"]
    common = dict(n_items=sizes["n_items"], seq_len=sizes["max_len"],
                  batch_size=batch)
    if data["generator"] == "SequenceDataset":
        return SequenceDataset(SeqDataConfig(**common))
    if data["generator"] == "LongTailDataset":
        return LongTailDataset(LongTailConfig(**common,
                                              zipf_a=data["zipf_a"]))
    raise ConfigMismatch(f"unknown data generator {data['generator']!r}")


def model_config(config):
    from repro.models.sasrec import SeqRecConfig

    s = config["sizes"]
    return SeqRecConfig(
        n_items=s["n_items"], max_len=s["max_len"], d_model=s["d_model"],
        n_layers=s["n_layers"], n_heads=s["n_heads"], d_ff=s["d_ff"],
        dropout=s["dropout"], causal=True,
        row_pad_multiple=s["row_pad_multiple"], dtype=config["dtype"])


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    return [jnp.sqrt(jnp.sum(jnp.square(a))) for a in jax.tree.leaves(tree)]


def _by_path(tree, values):
    import jax

    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    return {p: float(v) for p, v in zip(paths, values)}


def reference_inputs(config, traffic, seed: int, half_batch: bool = False):
    """What a cell at ``seed`` feeds its first steps, made as the cell
    makes it: the weights' key, the generator's first batches and the
    step keys. ``half_batch`` plants a fault: the second half of each
    batch's rows left out of the loss (the mean taken over the rest)."""
    import jax
    import jax.numpy as jnp

    from repro.data import Cursor

    batch = traffic["batch"]
    k_init, key = jax.random.split(seeds.key(seed))
    data, cursor = dataset(config, batch), Cursor(seed=seed)
    batches, keys = [], []
    for _ in range(CHECK_STEPS):
        host, cursor = data.next_batch(cursor)
        key, sk = jax.random.split(key)
        if half_batch:
            host["valid"][batch // 2:] = False
        batches.append({k: jnp.asarray(v) for k, v in host.items()})
        keys.append(sk)
    return k_init, batches, keys


def reference_readings(config, traffic, k_init, batches, keys,
                       mode: str = "highest"):
    """The reference's readings of the first steps, at ``mode``."""
    import jax

    from reference import sasrec as ref

    want = expected_sce(config, traffic["batch"] * config["sizes"]["max_len"])
    with jax.default_matmul_precision("highest"):
        return ref.train_readings(k_init, config["sizes"], want,
                                  config["optimizer"], batches, keys, mode)


class Trainer:
    """The program's train step for a configuration, built once, and
    driven step by step as ``launch/train.py`` drives it."""

    def __init__(self, config, traffic):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        from reference import sasrec as ref
        from repro.configs import get_arch
        from repro.dist.sharding import batch_spec, replicated_sharding
        from repro.launch import steps as steps_lib
        from repro.launch.mesh import make_host_mesh

        self.config, sizes, opt = config, config["sizes"], config["optimizer"]
        self.batch = batch = traffic["batch"]
        self.positions = batch * sizes["max_len"]
        arch = get_arch(config["arch"])
        shape = arch.shape(traffic["shape"])
        self.cfg = model_config(config)
        self.sce = want = expected_sce(config, self.positions)
        if (shape.dims["batch"], arch.optimizer,
                arch.sce_bucket_size_y) != (batch, opt["name"], want["b_y"]):
            raise ConfigMismatch(
                f"{arch.name} {shape.name}: batch {shape.dims['batch']}, "
                f"{arch.optimizer}, b_y {arch.sce_bucket_size_y}; the "
                f"files state {batch}, {opt['name']}, {want['b_y']}")
        mesh = make_host_mesh(max_data=batch)
        step_fn, (opt_init, _), sce_cfg = steps_lib.make_seqrec_train_step(
            arch, self.cfg, mesh, shape, sce_mode=traffic["sce_mode"])
        got = {"n_b": sce_cfg.n_buckets, "b_x": sce_cfg.bucket_size_x,
               "b_y": sce_cfg.bucket_size_y}
        if got != want or not (sce_cfg.use_mix and sce_cfg.use_kernel):
            raise ConfigMismatch(f"program's SCE {sce_cfg} vs stated {want}")
        self.step_fn = jax.jit(step_fn, donate_argnums=(0, 1))
        rep = replicated_sharding(mesh)
        self.init = jax.jit(functools.partial(ref.init_params, sizes=sizes),
                            out_shardings=rep)
        self.opt_init = jax.jit(opt_init, out_shardings=rep)
        self.norms = jax.jit(_leaf_norms)
        self.delta_norms = jax.jit(lambda a, b: _leaf_norms(
            jax.tree.map(jnp.subtract, a, b)))
        self.place = lambda a: jax.device_put(
            a, NamedSharding(mesh, batch_spec(mesh, a.ndim)))
        self.data = dataset(config, batch)
        self.st = None

    def start(self, seed: int):
        """Fresh state from ``seed`` and its first steps. Returns the
        program's readings, and the fed batches and step keys."""
        import jax
        import numpy as np

        from repro.data import Cursor
        from repro.launch.elastic import DivergenceGuard

        self.st = None
        gc.collect()
        k_init, k_steps = jax.random.split(seeds.key(seed))
        params = self.init(k_init)
        self.st = {"params": params, "opt": self.opt_init(params),
                   "key": k_steps, "cursor": Cursor(seed=seed),
                   "guard": DivergenceGuard(), "data_s": 0.0, "skipped": 0}
        del params
        prog = {"losses": []}
        batches, keys = [], []
        for i in range(CHECK_STEPS):
            host, sk, loss = self.step()
            prog["losses"].append(loss)
            batches.append({k: np.array(v) for k, v in host.items()})
            keys.append(np.asarray(sk))
            if i == 0:  # AdamW's first moment after one step is (1-b1)·g
                m = self.st["opt"].inner["m"]
                b1 = self.config["optimizer"]["b1"]
                prog["grad"] = {p: v / (1 - b1) for p, v in
                                _by_path(m, self.norms(m)).items()}
                del m
        p0 = self.init(k_init)
        prog["delta"] = _by_path(p0, self.delta_norms(self.st["params"], p0))
        del p0
        self.st["data_s"] = 0.0
        return prog, k_init, batches, keys

    def step(self):
        """One step with the window's own feed: returns (host batch,
        step key, loss)."""
        import jax
        import jax.numpy as jnp

        st = self.st
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.data"):
            host, st["cursor"] = self.data.next_batch(st["cursor"])
        st["data_s"] += time.monotonic() - t0
        st["key"], sk = jax.random.split(st["key"])
        dev = {k: self.place(v) for k, v in host.items()}
        dev["loss_cap"] = jnp.float32(st["guard"].loss_cap())
        with jax.profiler.TraceAnnotation("bench.step"):
            st["params"], st["opt"], metrics = self.step_fn(
                st["params"], st["opt"], dev, sk)
        with jax.profiler.TraceAnnotation("bench.sync"):
            loss = float(metrics["loss"])
            skipped = bool(metrics["skipped"])
        st["skipped"] += skipped
        st["guard"].observe(loss, skipped=skipped)
        return host, sk, loss

    def close(self):
        self.st = self.step_fn = None
        gc.collect()


def _judge(config, traffic, prog, k_init, batches, keys):
    import jax.numpy as jnp

    want = reference_readings(
        config, traffic, k_init,
        [{k: jnp.asarray(v) for k, v in b.items()} for b in batches],
        [jnp.asarray(k) for k in keys])
    return checks.train_gaps(prog, want)


def run(ctx: cell.Context) -> cell.Run:
    import jax

    config, traffic = ctx.spec.config, ctx.spec.traffic
    t = Trainer(config, traffic)
    ctx.mark("build")
    prog, k_init, batches, keys = t.start(ctx.seed)
    check_skipped = t.st["skipped"]

    cell.quiesce()
    setup_s = ctx.setup_s()
    steps = 0
    with cell.traced_window(ctx.trace, KERNELS) as box:
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.window"):
            while steps == 0 or time.monotonic() - t0 < ctx.seconds:
                t.step()
                steps += 1
        window_s = time.monotonic() - t0
    peak = cell.memory_peak_bytes(ctx.devices)
    skipped, data_s = t.st["skipped"], t.st["data_s"]
    t.close()
    gaps, notes = _judge(config, traffic, prog, k_init, batches, keys)
    return cell.Run(
        attempted=steps, failed=skipped,
        end_to_end={
            "train_positions_per_s": t.positions * steps / window_s,
            "train_peak_hbm_gb": peak / 1e9,
            "setup_s": setup_s,
        },
        checks=gaps, notes=notes,
        counters={"steps": steps, "window_s": window_s, "data_s": data_s,
                  "positions": t.positions,
                  "catalog": t.cfg.catalog_loss_size, **t.sce},
        memory_peak_bytes=peak,
        trace=box["trace"],
    )


class Readings:
    """The compared numbers of sound runs on many seeds in one process:
    one step object, fresh state and first steps per seed."""

    def __init__(self, config, traffic):
        self.config, self.traffic = config, traffic
        self.trainer = Trainer(config, traffic)

    def __call__(self, seed: int):
        prog, k_init, batches, keys = self.trainer.start(seed)
        self.trainer.st = None
        return _judge(self.config, self.traffic, prog, k_init, batches, keys)


def control_numbers(config, traffic, seed: int, mode: str, seconds: float):
    """The compared numbers, and the notes on them, with the reference in
    the program's place at ``mode`` — or, with ``mode="half_batch"``, at
    ``highest`` with that fault planted."""
    k_init, batches, keys = reference_inputs(config, traffic, seed)
    want = reference_readings(config, traffic, k_init, batches, keys)
    if mode == "half_batch":
        got = reference_readings(
            config, traffic, *reference_inputs(config, traffic, seed, True))
    else:
        got = reference_readings(config, traffic, k_init, batches, keys,
                                 mode)
    return checks.train_gaps(got, want)
