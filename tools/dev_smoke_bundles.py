import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
import jax
import jax.numpy as jnp

from repro.configs import base
from repro.configs.base import ShapeConfig
from repro.core.topology import make_mesh
from repro.runtime import steps

mesh_single = make_mesh((4, 2), ("data", "model"))
mesh_multi = make_mesh((2, 2, 2), ("pod", "data", "model"))

SHAPES = {
    "train": ShapeConfig("train_4k", 64, 8, "train"),
    "prefill": ShapeConfig("prefill_32k", 128, 4, "prefill"),
    "decode": ShapeConfig("decode_32k", 128, 8, "decode"),
    "long": ShapeConfig("long_500k", 256, 2, "decode"),
}

archs = base.list_architectures() if len(sys.argv) < 2 else [sys.argv[1]]
for arch in archs:
    cfg = base.get_smoke_config(arch)
    for sname, shape in SHAPES.items():
        if sname == "long" and cfg.long_context == "skip":
            print(f"{arch}:{sname}: SKIP (policy)")
            continue
        for mesh, mp in ((mesh_single, False), (mesh_multi, True)):
            tag = f"{arch}:{sname}:{'multi' if mp else 'single'}"
            try:
                import repro.runtime.steps as S
                kind = shape.kind
                if kind == "train":
                    mode = steps.train_mode_for(arch, mp)
                    if mode == "admm":
                        b = steps.make_admm_train_bundle(
                            cfg, shape, mesh, multi_pod=mp, arch=arch)
                    else:
                        b = steps.make_fsdp_train_bundle(
                            cfg, shape, mesh, multi_pod=mp)
                elif kind == "prefill":
                    b = steps.make_prefill_bundle(cfg, shape, mesh,
                                                  multi_pod=mp, arch=arch)
                else:
                    b = steps.make_serve_bundle(
                        cfg, shape, mesh, multi_pod=mp, arch=arch,
                        long_context=(sname == "long"))
                lowered = b.lower()
                compiled = lowered.compile()
                print(f"{tag}: OK")
            except Exception as e:
                print(f"{tag}: FAIL {type(e).__name__}: {str(e)[:300]}")
