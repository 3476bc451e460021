"""Step bundles lower + compile on a small multi-device mesh.

The 512-device production dry-run lives in its own process
(repro.launch.dryrun); here a subprocess with 8 placeholder devices checks
the bundle machinery (this test file must NOT set XLA_FLAGS in-process —
other tests need the default single device).
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.configs import base
    from repro.configs.base import ShapeConfig
    from repro.core.topology import make_mesh
    from repro.runtime import steps

    mesh_single = make_mesh((4, 2), ("data", "model"))
    mesh_multi = make_mesh((2, 2, 2), ("pod", "data", "model"))
    shapes = {
        "train": ShapeConfig("train_4k", 64, 8, "train"),
        "prefill": ShapeConfig("prefill_32k", 128, 4, "prefill"),
        "decode": ShapeConfig("decode_32k", 128, 8, "decode"),
        "long": ShapeConfig("long_500k", 256, 2, "decode"),
    }
    results = {}
    for arch in ["tinyllama-1.1b", "olmoe-1b-7b", "zamba2-7b"]:
        cfg = base.get_smoke_config(arch)
        for sname, shape in shapes.items():
            for mesh, mp in ((mesh_single, False), (mesh_multi, True)):
                tag = f"{arch}:{sname}:{'m' if mp else 's'}"
                kind = shape.kind
                if kind == "train":
                    b = steps.make_admm_train_bundle(
                        cfg, shape, mesh, multi_pod=mp, arch=arch)
                elif kind == "prefill":
                    b = steps.make_prefill_bundle(cfg, shape, mesh,
                                                  multi_pod=mp, arch=arch)
                else:
                    b = steps.make_serve_bundle(
                        cfg, shape, mesh, multi_pod=mp, arch=arch,
                        long_context=(sname == "long"))
                compiled = b.lower().compile()
                results[tag] = compiled.cost_analysis() is not None
        # the scheduler's paged decode step (serve shapes lower this now);
        # the multi-pod variant lowers the temperature-sampling path
        for mesh, mp in ((mesh_single, False), (mesh_multi, True)):
            b = steps.make_paged_serve_bundle(
                cfg, shapes["decode"], mesh, multi_pod=mp, arch=arch,
                page_size=16, sample=("temp" if mp else "greedy"),
                temperature=0.8)
            compiled = b.lower().compile()
            results[f"{arch}:paged:{'m' if mp else 's'}"] = \\
                compiled.cost_analysis() is not None
    print("RESULTS=" + json.dumps(results))
""")


@pytest.mark.slow
def test_bundles_lower_and_compile():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULTS=")][-1]
    results = json.loads(line[len("RESULTS="):])
    assert len(results) == 3 * 5 * 2
    assert all(results.values())


BACKEND_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.configs import base
    from repro.configs.base import ShapeConfig
    from repro.core.topology import make_mesh
    from repro.runtime import steps

    cfg = base.get_smoke_config("tinyllama-1.1b")
    shape = ShapeConfig("train_4k", 64, 8, "train")
    mesh_single = make_mesh((4, 2), ("data", "model"))
    mesh_multi = make_mesh((2, 2, 2), ("pod", "data", "model"))
    results = {}
    for backend in ("dense", "sparse", "sharded"):
        os.environ["REPRO_ADMM_MIX_BACKEND"] = backend
        for mesh, mp in ((mesh_single, False), (mesh_multi, True)):
            tag = f"{backend}:{'m' if mp else 's'}"
            b = steps.make_admm_train_bundle(cfg, shape, mesh,
                                             multi_pod=mp,
                                             arch="tinyllama-1.1b")
            results[tag] = b.lower().compile().cost_analysis() is not None
    print("RESULTS=" + json.dumps(results))
""")


@pytest.mark.slow
def test_admm_bundle_compiles_per_mix_backend():
    """The production ADMM bundle lowers + compiles under every topology
    backend (REPRO_ADMM_MIX_BACKEND) on single- and multi-pod meshes —
    in particular the sharded backend's fully-manual shard_map must
    compose with the TP/FSDP shardings inside each worker replica."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_ADMM_MIX_BACKEND", None)
    proc = subprocess.run([sys.executable, "-c", BACKEND_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULTS=")][-1]
    results = json.loads(line[len("RESULTS="):])
    assert len(results) == 6
    assert all(results.values()), results


def test_train_mode_selection():
    from repro.runtime.steps import train_mode_for
    assert train_mode_for("grok-1-314b", multi_pod=False) == "fsdp"
    assert train_mode_for("grok-1-314b", multi_pod=True) == "admm"
    assert train_mode_for("tinyllama-1.1b", multi_pod=False) == "admm"


def test_supports_policy():
    from repro.configs import base
    from repro.runtime.steps import supports
    wcfg = base.get_config("whisper-small")
    assert not supports("whisper-small", wcfg,
                        base.INPUT_SHAPES["long_500k"])
    assert supports("whisper-small", wcfg, base.INPUT_SHAPES["decode_32k"])
    zcfg = base.get_config("zamba2-7b")
    assert supports("zamba2-7b", zcfg, base.INPUT_SHAPES["long_500k"])
