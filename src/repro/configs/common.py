"""Arch/shape registry shared by all assigned-architecture configs.

Every ``src/repro/configs/<id>.py`` registers one :class:`ArchSpec` — the
exact published configuration, its input-shape set, a reduced smoke
config, and training policy (loss, optimizer, dtype, FSDP, microbatching).
``launch/cells.py`` turns an (arch, shape, mesh) triple into a concrete
step function + abstract inputs for the dry-run.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

_REGISTRY: Dict[str, "ArchSpec"] = {}


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str  # train | prefill | decode | serve | retrieval | train_sampled
    dims: Mapping[str, int]
    note: str = ""
    skip: Optional[str] = None  # reason string for documented skips


@dataclasses.dataclass(frozen=True)
class ArchSpec:
    name: str
    family: str  # lm | seqrec | gnn | recsys
    paper_ref: str
    make_config: Callable[[str], Any]  # shape name -> full model config
    make_smoke_config: Callable[[], Any]  # reduced config for CPU tests
    shapes: Tuple[ShapeSpec, ...]
    optimizer: str = "adamw"
    # the optimizer's learning rate and settings (make_optimizer kwargs)
    optimizer_args: Mapping[str, float] = dataclasses.field(
        default_factory=lambda: {"lr": 3e-4})
    train_loss: str = "sce"  # lm/seqrec only
    dtype: str = "float32"
    fsdp: bool = False
    # gradient-accumulation factor per shape name (1 = none)
    microbatches: Mapping[str, int] = dataclasses.field(default_factory=dict)
    # dtype of the microbatch gradient accumulator. f32 default; the
    # 1T-param arch accumulates in bf16 (a f32 accumulator alone would be
    # 4 bytes/param — 16 GB/device at 512 chips).
    accum_dtype: str = "float32"
    sce_bucket_size_y: int = 512
    # In-loop evaluation protocol (repro.eval): "leave-one-out" (seqrec
    # — one held-out item per user), "token-rank" (lm — every next-token
    # position against the full vocab), or None (no streaming eval
    # protocol defined; --eval-every warns loudly and skips).
    eval_protocol: Optional[str] = None
    notes: str = ""

    def shape(self, name: str) -> ShapeSpec:
        for s in self.shapes:
            if s.name == name:
                return s
        raise KeyError(f"{self.name} has no shape {name!r}")

    def runnable_shapes(self) -> Tuple[ShapeSpec, ...]:
        return tuple(s for s in self.shapes if s.skip is None)


def register(spec: ArchSpec) -> ArchSpec:
    _REGISTRY[spec.name] = spec
    return spec


_ARCH_MODULES = [
    "deepseek_coder_33b",
    "yi_6b",
    "gemma2_2b",
    "kimi_k2",
    "granite_moe",
    "schnet",
    "dcn_v2",
    "dlrm_rm2",
    "bert4rec",
    "xdeepfm",
    "sasrec_sce",
    "moonlight",
]


def _load_all() -> None:
    for mod in _ARCH_MODULES:
        importlib.import_module(f"repro.configs.{mod}")


def get_arch(name: str) -> ArchSpec:
    if not _REGISTRY:
        _load_all()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_archs() -> Tuple[str, ...]:
    if not _REGISTRY:
        _load_all()
    return tuple(_REGISTRY)


# ---------------------------------------------------------------------------
# The four LM shapes (shared by the 5 LM archs) and the recsys shape set
# ---------------------------------------------------------------------------
def lm_shapes(*, long_ctx_skip: Optional[str]) -> Tuple[ShapeSpec, ...]:
    return (
        ShapeSpec("train_4k", "train", {"seq_len": 4096, "global_batch": 256}),
        ShapeSpec(
            "prefill_32k", "prefill", {"seq_len": 32768, "global_batch": 32}
        ),
        ShapeSpec(
            "decode_32k", "decode", {"seq_len": 32768, "global_batch": 128}
        ),
        ShapeSpec(
            "long_500k",
            "decode",
            {"seq_len": 524288, "global_batch": 1},
            skip=long_ctx_skip,
        ),
    )


def recsys_shapes() -> Tuple[ShapeSpec, ...]:
    return (
        ShapeSpec("train_batch", "train", {"batch": 65536}),
        ShapeSpec("serve_p99", "serve", {"batch": 512}),
        ShapeSpec("serve_bulk", "serve", {"batch": 262144}),
        ShapeSpec(
            "retrieval_cand",
            "retrieval",
            {"batch": 1, "n_candidates": 1_000_000},
        ),
    )
