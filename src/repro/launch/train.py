"""Fault-tolerant training driver (DESIGN.md §8).

The same step factories the dry-run lowers are executed here with real
arrays. Production behavior:

  * **auto-restore**: on start, the latest checkpoint that PASSES
    manifest verification (params, opt state, PRNG key, data cursor) is
    restored — a corrupt or torn latest step is skipped with a warning,
    never loaded (CheckpointManager's fallback ladder); a crashed job
    relaunches and continues from the last intact atomic commit.
  * **async checkpointing** under a combined step- (``--ckpt-every``) +
    wall-clock- (``--ckpt-interval-s``) save policy (host snapshot +
    background write; the step loop never blocks on I/O).
  * **preemption**: SIGTERM/SIGINT finish the in-flight step, take a
    final *blocking* save, and exit with ``elastic.EXIT_PREEMPTED`` (42)
    so the launcher can distinguish "clean preemption — relaunch" from
    a crash. ``kill -9`` needs no cooperation: the atomic-rename commit
    protocol means relaunch resumes from the last completed write.
  * **divergence guard**: non-finite or above-cap losses skip the param
    AND optimizer update on-device (``steps._apply_update_guarded``);
    ``--max-strikes`` consecutive bad steps roll back to the last
    verified checkpoint with a reseeded data offset
    (``elastic.DivergenceGuard``) instead of training on poisoned state.
  * **straggler watchdog**: steps slower than ``watchdog × median`` are
    logged; with ``--skip-stragglers`` the *data load* of the next step
    reuses the previous host batch (bounded staleness) instead of
    blocking on a slow input shard.
  * **elastic restart**: checkpoints are host-gathered and the data
    cursor stores only the global ``(seed, step)``, so ``--ckpt-dir``
    written on one mesh/host count restores onto any other: with
    ``--n-hosts H`` the device batch is assembled from H per-host
    ``ShardedCursor`` slices whose concatenation is bit-identical to
    the global stream for every H (single-process emulation of the
    per-host sharded input pipeline — the resharding drill resumes an
    H-host checkpoint at H′ and the loss curve doesn't move).
  * optional **int8 error-feedback gradient compression** models the
    cross-pod DCI payload (--grad-compression int8).
  * **periodic in-loop evaluation** (``--eval-every``) through
    ``repro.eval``, dispatched on ``ArchSpec.eval_protocol``.
  * ``--metrics-file`` appends one JSON line per completed step
    (step/loss/skipped/grad_norm) — the kill-drills diff these curves
    step-for-step across kill/restore boundaries.

By default each arch's reduced smoke config trains (CPU-runnable);
``--shape <ShapeSpec name>`` selects the arch's full config
(``make_config``) at that registered shape's dims instead — e.g.
sasrec-sce ``train_paper``: batch 128 × 200 over the 173,511-item
Gowalla catalog.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch sasrec-sce \
      --steps 50 --ckpt-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch sasrec-sce \
      --shape train_paper --steps 5
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.data import (
    ClickDataConfig,
    ClickstreamDataset,
    Cursor,
    SeqDataConfig,
    SequenceDataset,
    ShardedCursor,
    batched_molecules,
)
from repro.dist.sharding import batch_spec, replicated_sharding
from repro.kernels import guard as kguard
from repro.launch import steps as steps_lib
from repro.launch.elastic import (
    EXIT_PREEMPTED,
    DivergenceGuard,
    PreemptionHandler,
    TrainState,
)
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh


@dataclasses.dataclass
class SmokeShape:
    """Reduced stand-in for a ShapeSpec (CPU-runnable)."""

    name: str
    kind: str
    dims: Dict[str, int]


def _setup(arch, batch: int, seq_len: int, shape_name: Optional[str] = None):
    """(model cfg, shape, data source) for a training run: the reduced
    smoke config at ``batch``/``seq_len``, or — with ``shape_name`` — the
    arch's full config at that registered shape's dims."""
    if shape_name is not None:
        shape = arch.shape(shape_name)
        cfg = arch.make_config(shape_name)
        batch = shape.dims.get("global_batch", shape.dims.get("batch"))
        seq_len = shape.dims.get("seq_len", seq_len)
    else:
        shape = None
        cfg = arch.make_smoke_config()
    if arch.family == "lm":
        shape = shape or SmokeShape(
            "train_smoke", "train",
            {"global_batch": batch, "seq_len": seq_len},
        )
        data = SequenceDataset(SeqDataConfig(
            n_items=cfg.vocab, seq_len=seq_len, batch_size=batch,
            min_len_frac=1.0,
        ))
        return cfg, shape, data
    if arch.family == "seqrec":
        shape = shape or SmokeShape("train_smoke", "train", {"batch": batch})
        data = SequenceDataset(SeqDataConfig(
            n_items=cfg.n_items, seq_len=cfg.max_len, batch_size=batch,
        ))
        return cfg, shape, data
    if arch.family == "recsys":
        shape = shape or SmokeShape("train_smoke", "train", {"batch": batch})
        data = ClickstreamDataset(ClickDataConfig(
            vocab_sizes=cfg.vocab_sizes, batch_size=batch,
            n_dense=getattr(cfg, "n_dense", 1),
        ))
        return cfg, shape, data
    # gnn (molecule regime for smoke)
    shape = shape or SmokeShape(
        "molecule", "train",
        {"batch": batch, "n_nodes": 10, "n_edges": 20, "d_feat": cfg.d_feat},
    )
    return cfg, shape, None


def _init_params(arch, cfg, key):
    from repro.models import bert4rec as b4r
    from repro.models import recsys as recsys_lib
    from repro.models import sasrec, schnet, transformer

    if arch.family == "lm":
        return transformer.init_params(key, cfg)
    if arch.family == "seqrec":
        return (b4r if not cfg.causal else sasrec).init_params(key, cfg)
    if arch.family == "recsys":
        init = {
            "dcn-v2": recsys_lib.init_dcn_v2,
            "dlrm-rm2": recsys_lib.init_dlrm,
            "xdeepfm": recsys_lib.init_xdeepfm,
        }[arch.name]
        return init(key, cfg)
    return schnet.init_params(key, cfg)


def _make_step(arch, cfg, mesh, shape, sce_mode, grad_compression=None):
    if arch.family == "lm":
        step, opt, _ = steps_lib.make_lm_train_step(
            arch, cfg, mesh, shape, sce_mode=sce_mode,
            grad_compression=grad_compression,
        )
    elif arch.family == "seqrec":
        step, opt, _ = steps_lib.make_seqrec_train_step(
            arch, cfg, mesh, shape, sce_mode=sce_mode,
            grad_compression=grad_compression,
        )
    elif arch.family == "recsys":
        step, opt = steps_lib.make_recsys_train_step(
            arch, cfg, mesh, shape, grad_compression=grad_compression
        )
    else:
        step, opt = steps_lib.make_gnn_train_step(arch, cfg, mesh, shape)
    return step, opt


def _host_batch(arch, data, cursor, shape, cfg, n_hosts: int = 1):
    """Next host batch at ``cursor``.

    With ``n_hosts > 1`` each emulated host independently produces its
    local slice through its own :class:`ShardedCursor` and the device
    batch is their concatenation — bit-identical to the 1-host global
    batch for every H (the property the resharding drill pins), while
    actually exercising the per-host sharded code path.
    """
    if arch.family == "gnn":
        return batched_molecules(
            cursor,
            n_mols=shape.dims["batch"],
            nodes_per_mol=shape.dims["n_nodes"],
            edges_per_mol=shape.dims["n_edges"],
            d_feat=shape.dims["d_feat"],
        )
    if n_hosts == 1:
        batch, cur = data.next_batch(cursor)
    else:
        parts = [
            data.next_batch_sharded(
                ShardedCursor(cursor, host_id=h, n_hosts=n_hosts)
            )[0]
            for h in range(n_hosts)
        ]
        batch = {
            k: np.concatenate([p[k] for p in parts], axis=0)
            for k in parts[0]
        }
        cur = cursor.advance()
    if arch.family == "seqrec" and not getattr(cfg, "causal", True):
        batch = {"tokens": batch["tokens"]}  # bert4rec masks in-step
    return batch, cur


def train(
    arch_name: str,
    *,
    steps: int = 50,
    batch: int = 8,
    seq_len: int = 32,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 20,
    ckpt_interval_s: Optional[float] = None,
    keep_n: int = 3,
    seed: int = 0,
    sce_mode: str = "exact",
    grad_compression: Optional[str] = None,
    watchdog: float = 5.0,
    skip_stragglers: bool = False,
    log_every: int = 10,
    eval_every: int = 0,
    eval_users: int = 128,
    n_hosts: int = 1,
    max_strikes: int = 3,
    guard_factor: float = 100.0,
    metrics_file: Optional[str] = None,
    chaos_nan_at: Optional[int] = None,
    guard_policy: Optional[str] = None,
    shape: Optional[str] = None,
) -> Dict[str, Any]:
    """Run a real training loop; returns final metrics.

    ``shape`` names one of the arch's registered ShapeSpecs: the full
    config at its dims replaces the smoke config and ``batch``/
    ``seq_len``.

    ``chaos_nan_at`` is the fault-injection hook the divergence drill
    uses: at that host step the params are multiplied by NaN *once*,
    which must be survived (update skipped on-device, strikes, rollback
    to the last verified checkpoint) — never shipped.

    ``guard_policy`` (``--guard``) sets the process-wide kernel-guard
    policy (``repro.kernels.guard``): ``off`` / ``warn`` (default) /
    ``strict``. Under warn/strict the loss threads per-kernel numerics
    sentinels into the step metrics, so a divergence-guard strike names
    WHICH kernel went non-finite.
    """
    arch = get_arch(arch_name)
    if guard_policy is not None:
        kguard.set_policy(guard_policy)
    if n_hosts > 1 and arch.family == "gnn":
        raise ValueError("--n-hosts emulation needs a sharded dataset; "
                         "the gnn molecule stream has none")
    cfg, shape, data = _setup(arch, batch, seq_len, shape)
    batch = shape.dims.get("global_batch", shape.dims.get("batch"))
    seq_len = shape.dims.get("seq_len", seq_len)
    if n_hosts > 1 and batch % n_hosts:
        raise ValueError(f"batch {batch} not divisible by n_hosts {n_hosts}")
    mesh = make_host_mesh(max_data=batch)
    step_fn, (opt_init, _) = _make_step(
        arch, cfg, mesh, shape, sce_mode, grad_compression
    )
    jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

    # Params and optimizer state live replicated on the mesh from the
    # start: the step's outputs come back that way, so step 0 compiles
    # the same program every later step runs.
    on_mesh = lambda tree: jax.device_put(tree, replicated_sharding(mesh))
    key = jax.random.PRNGKey(seed)
    params = on_mesh(_init_params(arch, cfg, key))
    state = TrainState(
        params=params,
        opt_state=on_mesh(opt_init(params)),
        key=key,
        cursor=Cursor(seed=seed),
        step=-1,
    )
    mgr = (
        CheckpointManager(
            ckpt_dir,
            keep_n=keep_n,
            save_every_steps=ckpt_every,
            save_interval_seconds=ckpt_interval_s,
        )
        if ckpt_dir
        else None
    )

    def _restore_or(state):
        """Newest verified checkpoint, or ``state`` unchanged."""
        last, tree = mgr.restore_latest()
        if last is None:
            return state, None
        restored = TrainState.from_ckpt(
            tree, opt_template=opt_init(state.params)
        )
        restored.params = on_mesh(restored.params)
        restored.opt_state = on_mesh(restored.opt_state)
        print(f"[restore] resumed from step {last}")
        return restored, last

    if mgr is not None:
        state, _ = _restore_or(state)

    # Periodic unsampled eval, dispatched on the arch's declared
    # protocol (configs.common.ArchSpec.eval_protocol): streaming
    # rank-and-topk over a held-out stream, sharded over the mesh when
    # model-parallel. "leave-one-out" scores one held-out item per user
    # (seqrec); "token-rank" scores EVERY next-token position against
    # the full vocabulary (lm) — no (rows, C) score matrix either way.
    protocol = arch.eval_protocol
    do_eval = eval_every > 0 and protocol is not None
    if eval_every > 0 and protocol is None:
        print(
            f"[eval] WARNING: --eval-every {eval_every} requested, but "
            f"arch {arch.name!r} (family {arch.family!r}) defines no "
            f"eval protocol — in-loop evaluation is SKIPPED. Set "
            f"ArchSpec.eval_protocol ('leave-one-out' or 'token-rank') "
            f"to enable it."
        )
    eval_metrics: Dict[str, float] = {}
    if do_eval:
        from repro.data import SeqDataConfig as _SDC
        from repro.data import SequenceDataset as _SD
        from repro.eval import evaluate_streaming, evaluate_streaming_lm

        if protocol == "token-rank":
            eval_data = _SD(_SDC(
                n_items=cfg.vocab, seq_len=seq_len,
                batch_size=eval_users, min_len_frac=1.0,
            ))
            eval_batch, _ = eval_data.heldout_batch(Cursor(seed=seed))
        else:  # leave-one-out
            eval_data = _SD(_SDC(
                n_items=cfg.n_items, seq_len=cfg.max_len,
                batch_size=eval_users,
            ))
            eval_batch, _ = eval_data.eval_batch(Cursor(seed=seed))
        eval_mesh = mesh if mesh.shape.get("model", 1) > 1 else None

    guard = DivergenceGuard(max_strikes=max_strikes,
                            cap_factor=guard_factor)
    metrics_fh = open(metrics_file, "a") if metrics_file else None
    chaos_fired = False

    def record(step, loss, skipped, grad_norm, sentinels=None):
        if metrics_fh is None:
            return
        row = {
            "step": step, "loss": loss, "skipped": skipped,
            "grad_norm": grad_norm,
        }
        if sentinels:
            row["sentinels"] = sentinels
        metrics_fh.write(json.dumps(row) + "\n")
        metrics_fh.flush()

    def save_state(blocking: bool):
        mgr.save(
            state.step,
            state.to_ckpt(n_hosts=n_hosts),
            blocking=blocking,
        )

    def place(a):
        """Host array → device, batch rows split over the data axes
        (the gnn batch is a flat segment layout and stays whole)."""
        a = np.asarray(a)
        if arch.family == "gnn" or a.ndim == 0:
            return jnp.asarray(a)
        return jax.device_put(a, NamedSharding(mesh, batch_spec(mesh, a.ndim)))

    losses, times = [], []
    skipped_steps = 0
    preempted = False
    prev_batch = None
    with jax.set_mesh(mesh), PreemptionHandler() as preemption:
        step = state.step + 1
        while step < steps:
            if preemption.preempted:
                preempted = True
                break
            if chaos_nan_at is not None and step == chaos_nan_at \
                    and not chaos_fired:
                chaos_fired = True
                print(f"[chaos] step {step}: poisoning params with NaN")
                state.params = jax.tree.map(
                    lambda p: (p * jnp.nan).astype(p.dtype)
                    if jnp.issubdtype(p.dtype, jnp.floating) else p,
                    state.params,
                )
            t0 = time.time()
            host_batch, new_cursor = _host_batch(
                arch, data, state.cursor, shape, cfg, n_hosts
            )
            t_data = time.time() - t0
            # Straggler mitigation: if data loading stalls, reuse the
            # previous batch (bounded staleness) instead of blocking.
            if (
                skip_stragglers
                and prev_batch is not None
                and times
                and t_data > watchdog * statistics.median(times)
            ):
                host_batch = prev_batch
                print(f"[watchdog] step {step}: slow input shard "
                      f"({t_data:.2f}s) — reusing previous batch")
                new_cursor = state.cursor
            else:
                prev_batch = host_batch

            state.key, step_key = jax.random.split(state.key)
            dev_batch = jax.tree.map(place, host_batch)
            dev_batch["loss_cap"] = jnp.float32(guard.loss_cap())
            state.params, state.opt_state, metrics = jit_step(
                state.params, state.opt_state, dev_batch, step_key
            )
            loss = float(metrics["loss"])
            skipped = bool(metrics.get("skipped", False))
            grad_norm = float(metrics.get("grad_norm", np.nan))
            # Tripped numerics sentinels (kernels/guard): nonzero
            # per-kernel counters naming what went non-finite on-device.
            tripped = {
                k: int(v)
                for k, v in metrics.get("sentinels", {}).items()
                if int(v)
            }
            state.cursor = new_cursor
            state.step = step
            dt = time.time() - t0
            losses.append(loss)
            times.append(dt)
            record(step, loss, skipped, grad_norm, tripped)

            verdict = guard.observe(loss, skipped=skipped)
            if verdict != "ok":
                skipped_steps += 1
                blame = (
                    f" (sentinels: {kguard.describe_sentinels(tripped)})"
                    if tripped else ""
                )
                print(f"[guard] step {step}: loss {loss:.4g} "
                      f"grad_norm {grad_norm:.4g} — update skipped "
                      f"(strike {guard.strikes or guard.max_strikes}"
                      f"/{guard.max_strikes}){blame}")
            if verdict == "rollback":
                if mgr is None:
                    raise RuntimeError(
                        f"diverged for {guard.max_strikes} consecutive "
                        f"steps at step {step} and no --ckpt-dir to roll "
                        f"back to"
                    )
                mgr.wait()  # an in-flight async save must land first
                rolled, last = _restore_or(
                    dataclasses.replace(state)
                )
                if last is None:
                    raise RuntimeError(
                        "diverged and no intact checkpoint to roll "
                        "back to"
                    )
                state = rolled
                state.cursor = guard.reseed(state.cursor)
                print(f"[guard] rolled back to verified step {last} "
                      f"(rollback #{guard.rollbacks}, data offset "
                      f"+{guard.reseed_stride * guard.rollbacks})")
                step = state.step + 1
                continue

            if times and dt > watchdog * statistics.median(times):
                print(f"[watchdog] step {step} took {dt:.2f}s "
                      f"(median {statistics.median(times):.2f}s)")
            if step % log_every == 0:
                print(f"step {step:5d}  loss {loss:.4f}  {dt*1e3:.0f} ms")
            if do_eval and (step + 1) % eval_every == 0:
                if protocol == "token-rank":
                    eval_metrics = evaluate_streaming_lm(
                        state.params, cfg, eval_batch, mesh=eval_mesh
                    )
                else:
                    eval_metrics = evaluate_streaming(
                        state.params, cfg, eval_batch, mesh=eval_mesh
                    )
                shown = {k: round(v, 4) for k, v in eval_metrics.items()}
                print(f"[eval] step {step}: {shown}")
            if mgr is not None and mgr.should_save(step):
                save_state(blocking=False)
            step += 1
        if preemption.preempted and not preempted:
            preempted = True  # signal arrived during the final step

    if mgr is not None:
        mgr.wait()
        if preempted:
            # Final BLOCKING save of the exact current state so the
            # relaunch loses zero completed steps.
            save_state(blocking=True)
            print(f"[preempt] state saved at step {state.step}; "
                  f"exit {EXIT_PREEMPTED} to request relaunch")
    if metrics_fh is not None:
        metrics_fh.close()
    out: Dict[str, Any] = {
        "first_loss": losses[0] if losses else None,
        "final_loss": losses[-1] if losses else None,
        "losses": losses,
        "steps": len(losses),
        "mean_step_s": statistics.mean(times) if times else None,
        "skipped_steps": skipped_steps,
        "rollbacks": guard.rollbacks,
    }
    if preempted:
        out["preempted"] = True
        out["preempt_step"] = state.step
    if eval_metrics:
        out["eval"] = eval_metrics
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--ckpt-every", type=int, default=20,
                    help="step-based save interval")
    ap.add_argument("--ckpt-interval-s", type=float,
                    help="wall-clock save interval in seconds (combined "
                         "with --ckpt-every: whichever fires first)")
    ap.add_argument("--keep-n", type=int, default=3,
                    help="checkpoints retained (0 = all)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sce-mode", default="exact",
                    choices=["exact", "union", "gspmd"])
    ap.add_argument("--grad-compression", choices=["int8"])
    ap.add_argument("--skip-stragglers", action="store_true")
    ap.add_argument("--n-hosts", type=int, default=1,
                    help="emulated host count: the device batch is the "
                         "concat of per-host ShardedCursor slices; any "
                         "value yields the identical global stream")
    ap.add_argument("--max-strikes", type=int, default=3,
                    help="consecutive bad steps before rolling back to "
                         "the last verified checkpoint")
    ap.add_argument("--guard-factor", type=float, default=100.0,
                    help="divergence cap = factor x running median loss")
    ap.add_argument("--metrics-file",
                    help="append one JSON line per step (the kill-drill "
                         "loss-curve record)")
    ap.add_argument("--chaos-nan-at", type=int,
                    help="fault injection: poison params with NaN at "
                         "this step once (divergence drill)")
    ap.add_argument("--guard", choices=list(kguard.POLICIES),
                    help="kernel-guard policy (default: REPRO_GUARD env "
                         "or 'warn'): preflight block checks, "
                         "conformance-canary degradation, numerics "
                         "sentinels")
    ap.add_argument("--log-every", type=int, default=10,
                    help="print a progress line every N steps")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="run streaming unsampled eval every N steps "
                         "(seqrec: leave-one-out; lm: token-rank over "
                         "every position; 0 = off)")
    ap.add_argument("--eval-users", type=int, default=128,
                    help="held-out sequences per eval (lm: eval rows = "
                         "sequences x seq_len)")
    ap.add_argument("--shape",
                    help="registered ShapeSpec name (e.g. train_paper): "
                         "train the arch's full config at that shape's "
                         "dims instead of the smoke config")
    args = ap.parse_args()
    enable_compile_cache()
    out = train(
        args.arch,
        steps=args.steps,
        batch=args.batch,
        seq_len=args.seq_len,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        ckpt_interval_s=args.ckpt_interval_s,
        keep_n=args.keep_n,
        seed=args.seed,
        sce_mode=args.sce_mode,
        grad_compression=args.grad_compression,
        skip_stragglers=args.skip_stragglers,
        n_hosts=args.n_hosts,
        max_strikes=args.max_strikes,
        guard_factor=args.guard_factor,
        metrics_file=args.metrics_file,
        chaos_nan_at=args.chaos_nan_at,
        guard_policy=args.guard,
        log_every=args.log_every,
        eval_every=args.eval_every,
        eval_users=args.eval_users,
        shape=args.shape,
    )
    print(out)
    if out.get("preempted"):
        sys.exit(EXIT_PREEMPTED)


if __name__ == "__main__":
    main()
