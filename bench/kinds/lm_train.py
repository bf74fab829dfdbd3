"""LM train cells: the program's LM train step, built and driven as
``launch/train.py`` builds and drives it, at the configuration's matmul
precision (``bench/run.py`` sets it process-wide).

Set-up builds one object — the AOT-compiled, donated step of
``launch.steps.make_lm_train_step`` for the arch the configuration
names (checked against the file, key by key), its parameters (made on
the device from the seed by the reference's initializer, the router's
selection bias among them) and AdamW state — and drives it through its
first three steps with the window's own call and feed: a host batch
from the program's ``repro.data`` generator at the next cursor step
(``launch/train.py``'s LM data), placed on ``launch.mesh.make_host_mesh``,
then the step, then the loss and the expert counters read back. The
window goes on with the same object for ``--seconds``; with ``--trace
1`` its trace is reduced with the program's scopes
(``scopes.read_scoped`` on the compiled step's ``op_name``s) and
``layer_s`` beside ``reduce_scoped``'s keys: the device seconds under
each of ``LAYER_SCOPES``.

Afterwards the program's state is freed and the reference follows the
first three steps: the numbers of ``checks.train_gaps`` and
``route_flip_share``, the share of step 1's (token, slot) assignments
whose expert differs from the reference's, the program's read from
the compiled step's own ``moe_experts`` (step 1 only).
"""
from __future__ import annotations

import contextlib
import functools
import gc
import tempfile
import time

import numpy as np

from harness import cell, checks, scopes, seeds, trace
from kinds import train as base

KERNELS = base.KERNELS
LAYER_SCOPES = ("mla", "dense_ffn", "moe/route", "moe/experts",
                "moe/shared")
CHECK_STEPS = base.CHECK_STEPS
ConfigMismatch = base.ConfigMismatch


def program_config(config, traffic):
    """The arch, shape and model config the program trains for the
    configuration; raises :class:`ConfigMismatch` where a stated number
    differs from the program's."""
    from repro.configs import get_arch
    from repro.models import moe as moe_lib

    arch = get_arch(config["arch"])
    shape = arch.shape(traffic["shape"])
    cfg = arch.make_config(shape.name)
    m, e = cfg.mla, cfg.moe
    o = config["optimizer"]
    program = {
        "vocab_size": cfg.vocab_padded, "hidden_size": cfg.d_model,
        "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": cfg.n_dense_layers,
        "intermediate_size": cfg.d_ff, "num_attention_heads": cfg.n_heads,
        "q_lora_rank": m.q_lora_rank, "kv_lora_rank": m.kv_lora_rank,
        "qk_nope_head_dim": m.qk_nope_head_dim,
        "qk_rope_head_dim": m.qk_rope_head_dim,
        "v_head_dim": m.v_head_dim, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": None if cfg.rope_scaling is None else "yarn",
        "n_routed_experts": e.n_experts,
        "num_experts_per_tok": e.top_k,
        "moe_intermediate_size": e.d_ff,
        "n_shared_experts": e.n_shared_experts,
        "routed_scaling_factor": e.routed_scaling,
        "scoring_func": e.router, "experts_held": e.held,
        "aux_loss_weight": e.aux_loss_weight,
        "bias_update_rate": moe_lib.BIAS_UPDATE_RATE,
        "tie_word_embeddings": cfg.tie_embeddings, "dtype": cfg.dtype,
    }
    stated = {k: config[k] for k in program}
    stated["vocab_size"] = config["vocab_size"]
    diffs = {k: (v, stated[k]) for k, v in program.items()
             if v != stated[k]}
    if cfg.vocab != cfg.vocab_padded:
        diffs["vocab padding"] = (cfg.vocab_padded, cfg.vocab)
    opt = dict(arch.optimizer_args, name=arch.optimizer)
    if any(opt.get(k, v) != o[k] for k, v in
           (("name", None), ("lr", None), ("b1", 0.9), ("b2", 0.999),
            ("eps", 1e-8), ("weight_decay", 0.0))):
        diffs["optimizer"] = (opt, o)
    dims = (shape.dims["global_batch"], shape.dims["seq_len"])
    if dims != (traffic["batch"], traffic["seq_len"]):
        diffs["batch, seq_len"] = (dims, (traffic["batch"],
                                          traffic["seq_len"]))
    if arch.sce_bucket_size_y != config["sce"]["bucket_size_y"]:
        diffs["bucket_size_y"] = (arch.sce_bucket_size_y,
                                  config["sce"]["bucket_size_y"])
    if diffs:
        raise ConfigMismatch(f"{arch.name} {shape.name} (program, file): "
                             f"{diffs}")
    return arch, shape, cfg


def dataset(cfg, traffic):
    from repro.data import SeqDataConfig, SequenceDataset

    return SequenceDataset(SeqDataConfig(
        n_items=cfg.vocab, seq_len=traffic["seq_len"],
        batch_size=traffic["batch"], min_len_frac=1.0))


def _has_path(scope: str, parts) -> bool:
    """Whether ``parts`` occur in order among ``scope``'s components."""
    it = iter(scope.split("/"))
    return all(p in it for p in parts)


def layer_seconds(events) -> dict:
    """Device seconds under each of ``LAYER_SCOPES`` (its components in
    order in an operation's scope path: ``moe/experts`` is also
    ``moe/while/body/checkpoint/experts``), as the union of the
    intervals of its operations per device, averaged over devices."""
    ops = [e for e in events if e.kind == "op"]
    n_dev = max(1, len({e.device for e in ops}))
    out = {}
    for name in LAYER_SCOPES:
        parts = name.split("/")
        by_dev = {}
        for e in ops:
            if _has_path(getattr(e, "scope", ""), parts):
                by_dev.setdefault(e.device, []).append(
                    (e.start_ns, e.end_ns))
        out[name] = sum(b - a for ivs in by_dev.values()
                        for a, b in trace._union(ivs)) * 1e-9 / n_dev
    return out


@contextlib.contextmanager
def scoped_window(on: bool, op_names):
    """Trace the enclosed window when ``on``; afterwards ``box["trace"]``
    holds ``scopes.reduce_scoped`` of it with ``layer_s``, else
    ``None``. ``op_names``: the compiled step's module name and its
    instruction → ``op_name`` map."""
    box = {"trace": None}
    if not on:
        yield box
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        t0 = time.monotonic()
        try:
            yield box
        finally:
            window = time.monotonic() - t0
            jax.profiler.stop_trace()
        module, names = op_names
        events = scopes.read_scoped(trace.find_xplane(d), module, names)
        red = scopes.reduce_scoped(events, window, KERNELS)
        red["layer_s"] = layer_seconds(events)
        box["trace"] = red


class Trainer:
    """The program's LM train step for a configuration, compiled once,
    and driven step by step as ``launch/train.py`` drives it."""

    def __init__(self, config, traffic):
        import jax
        from jax.sharding import NamedSharding

        from reference import moonlight as ref
        from repro.dist.sharding import batch_spec, replicated_sharding
        from repro.launch import steps as steps_lib
        from repro.launch.mesh import make_host_mesh

        arch, shape, cfg = program_config(config, traffic)
        self.config, self.cfg = config, cfg
        self.tokens = traffic["batch"] * traffic["seq_len"]
        mesh = make_host_mesh(max_data=traffic["batch"])
        step_fn, (opt_init, _), sce_cfg = steps_lib.make_lm_train_step(
            arch, cfg, mesh, shape, sce_mode=traffic["sce_mode"])
        self.sce = want = base.expected_sce(config, self.tokens)
        got = {"n_b": sce_cfg.n_buckets, "b_x": sce_cfg.bucket_size_x,
               "b_y": sce_cfg.bucket_size_y}
        if got != want or not (sce_cfg.use_mix and sce_cfg.use_kernel):
            raise ConfigMismatch(f"program's SCE {sce_cfg} vs stated {want}")
        self.step_fn = jax.jit(step_fn, donate_argnums=(0, 1))
        self.compiled = self.op_names = None
        rep = replicated_sharding(mesh)
        self.init = jax.jit(functools.partial(ref.init_params, sizes=config),
                            out_shardings=rep)
        self.opt_init = jax.jit(opt_init, out_shardings=rep)
        self.norms = jax.jit(base._leaf_norms)
        self.delta_norms = jax.jit(lambda a, b: base._leaf_norms(
            jax.tree.map(jax.numpy.subtract, a, b)))
        self.place = lambda a: jax.device_put(
            a, NamedSharding(mesh, batch_spec(mesh, a.ndim)))
        self.data = dataset(cfg, traffic)
        self.st = None

    def start(self, seed: int):
        """Fresh state from ``seed`` and its first steps. Returns the
        program's readings, and the fed batches and step keys."""
        import jax

        from repro.data import Cursor
        from repro.launch.elastic import DivergenceGuard

        self.st = None
        gc.collect()
        k_init, k_steps = jax.random.split(seeds.key(seed))
        params = self.init(k_init)
        self.st = {"params": params, "opt": self.opt_init(params),
                   "key": k_steps, "cursor": Cursor(seed=seed),
                   "guard": DivergenceGuard(), "data_s": 0.0, "skipped": 0,
                   "routed": 0, "max_load": 0}
        del params
        prog = {"losses": []}
        batches, keys = [], []
        for i in range(CHECK_STEPS):
            host, sk, loss, metrics = self.step()
            prog["losses"].append(loss)
            batches.append({k: np.array(v) for k, v in host.items()})
            keys.append(np.asarray(sk))
            if i == 0:  # AdamW's first moment after one step is (1-b1)·g
                # the compiled step's choices, (layers, tokens, k)
                prog["routes"] = np.swapaxes(
                    np.asarray(metrics["moe_experts"]), 0, 1)
                m = self.st["opt"].inner["m"]
                b1 = self.config["optimizer"]["b1"]
                prog["grad"] = {p: v / (1 - b1) for p, v in
                                base._by_path(m, self.norms(m)).items()}
                del m
        p0 = self.init(k_init)
        prog["delta"] = base._by_path(
            p0, self.delta_norms(self.st["params"], p0))
        del p0
        self.st.update(data_s=0.0, routed=0, max_load=0)
        return prog, k_init, batches, keys

    def step(self):
        """One step with the window's own feed: returns (host batch,
        step key, loss, the step's metrics on the device)."""
        import jax

        st = self.st
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.data"):
            host, st["cursor"] = self.data.next_batch(st["cursor"])
        st["data_s"] += time.monotonic() - t0
        st["key"], sk = jax.random.split(st["key"])
        dev = {k: self.place(v) for k, v in host.items()}
        dev["loss_cap"] = jax.numpy.float32(st["guard"].loss_cap())
        if self.compiled is None:
            self.compiled = self.step_fn.lower(
                st["params"], st["opt"], dev, sk).compile()
            self.op_names = scopes.op_names_from_hlo(
                self.compiled.as_text())
        with jax.profiler.TraceAnnotation("bench.step"):
            st["params"], st["opt"], metrics = self.compiled(
                st["params"], st["opt"], dev, sk)
        with jax.profiler.TraceAnnotation("bench.sync"):
            got = jax.device_get({k: metrics[k] for k in (
                "loss", "skipped", "moe_routed", "moe_max_load")})
        loss, skipped = float(got["loss"]), bool(got["skipped"])
        st["skipped"] += skipped
        st["routed"] += int(got["moe_routed"])
        st["max_load"] += int(got["moe_max_load"])
        st["guard"].observe(loss, skipped=skipped)
        return host, sk, loss, metrics

    def close(self):
        self.st = None
        gc.collect()


def reference_inputs(config, traffic, seed: int, half_batch: bool = False):
    """What a cell at ``seed`` feeds its first steps, made as the cell
    makes it: the weights' key, the generator's first batches and the
    step keys. ``half_batch`` plants a fault: the second half of each
    sequence's positions left out of the loss."""
    import jax
    import jax.numpy as jnp

    from repro.data import Cursor
    from repro.configs import get_arch

    cfg = get_arch(config["arch"]).make_config(traffic["shape"])
    k_init, key = jax.random.split(seeds.key(seed))
    data, cursor = dataset(cfg, traffic), Cursor(seed=seed)
    batches, keys = [], []
    for _ in range(CHECK_STEPS):
        host, cursor = data.next_batch(cursor)
        key, sk = jax.random.split(key)
        if half_batch:
            host["valid"][:, traffic["seq_len"] // 2:] = False
        batches.append({k: jnp.asarray(v) for k, v in host.items()})
        keys.append(sk)
    return k_init, batches, keys


def reference_readings(config, traffic, k_init, batches, keys,
                       mode: str = "highest", faults=()):
    """The reference's readings of the first steps, at ``mode``, with
    ``faults`` planted."""
    import jax

    from reference import moonlight as ref

    want = base.expected_sce(config, traffic["batch"] * traffic["seq_len"])
    with jax.default_matmul_precision("highest"):
        out = ref.train_readings(k_init, config, want, config["optimizer"],
                                 batches, keys, mode, frozenset(faults))
    r = np.asarray(out.pop("routes"))
    out["routes"] = r.reshape(r.shape[0], -1, r.shape[-1])
    return out


def route_flip_share(got: np.ndarray, want: np.ndarray) -> float:
    """Share of the (layer, token, slot) assignments of ``got`` whose
    expert is not among the same token's in ``want``."""
    same = (got[..., :, None] == want[..., None, :]).any(-1)
    return float(1.0 - same.mean())


def _gaps(got, want):
    numbers, notes = checks.train_gaps(got, want)
    numbers["route_flip_share"] = route_flip_share(got["routes"],
                                                   want["routes"])
    return numbers, notes


def _judge(config, traffic, prog, k_init, batches, keys):
    import jax.numpy as jnp

    want = reference_readings(
        config, traffic, k_init,
        [{k: jnp.asarray(v) for k, v in b.items()} for b in batches],
        [jnp.asarray(k) for k in keys])
    return _gaps(prog, want)


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
    with scoped_window(ctx.trace, t.op_names) as box:
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.window"):
            while steps == 0 or time.monotonic() - t0 < ctx.seconds:
                t.step()
                steps += 1
        window_s = time.monotonic() - t0
    peak = cell.memory_peak_bytes(ctx.devices)
    skipped = t.st["skipped"] - check_skipped
    counters = {"steps": steps, "window_s": window_s,
                "data_s": t.st["data_s"], "tokens": t.tokens,
                "routed": t.st["routed"], "max_load": t.st["max_load"],
                "held": t.cfg.moe.held,
                "moe_layers": t.cfg.n_layers - t.cfg.n_dense_layers, **t.sce}
    t.close()  # the reference needs the chip's memory
    gaps, notes = _judge(config, traffic, prog, k_init, batches, keys)
    if box["trace"] is not None:  # the step's split, for the run's notes
        notes["scope_ms_per_step"] = {
            k: 1e3 * v / steps for k, v in box["trace"]["scope_s"].items()
            if k.count("/") < 2}
        notes["layer_ms_per_step"] = {
            k: 1e3 * v / steps for k, v in box["trace"]["layer_s"].items()}
    return cell.Run(
        attempted=steps, failed=skipped,
        end_to_end={
            "train_positions_per_s": t.tokens * steps / window_s,
            "train_peak_hbm_gb": peak / 1e9,
            "setup_s": setup_s,
        },
        checks=gaps, notes=notes, counters=counters,
        memory_peak_bytes=peak, trace=box["trace"],
    )


class Readings:
    """The compared numbers of sound runs on many seeds in one process:
    one step object, fresh state and first steps per seed."""

    def __init__(self, config, traffic):
        self.config, self.traffic = config, traffic
        self.trainer = Trainer(config, traffic)

    def __call__(self, seed: int):
        t = self.trainer
        prog, k_init, batches, keys = t.start(seed)
        t.close()
        return _judge(self.config, self.traffic, prog, k_init, batches,
                      keys)


def control_numbers(config, traffic, seed: int, mode: str, seconds: float):
    """The compared numbers, and the notes on them, with the reference in
    the program's place: at precision ``mode`` (``high``, ``bf16``), or
    at ``highest`` with a planted fault: ``half_batch`` or one of
    ``reference.moonlight.FAULTS``."""
    from reference import moonlight as ref

    k_init, batches, keys = reference_inputs(config, traffic, seed)
    want = reference_readings(config, traffic, k_init, batches, keys)
    if mode == "half_batch":
        got = reference_readings(
            config, traffic, *reference_inputs(config, traffic, seed, True))
    elif mode in ref.FAULTS:
        got = reference_readings(config, traffic, k_init, batches, keys,
                                 faults=(mode,))
    else:
        got = reference_readings(config, traffic, k_init, batches, keys,
                                 mode)
    return _gaps(got, want)
