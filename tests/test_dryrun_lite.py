"""Mini dry-run (subprocess, 16 forced host devices, 4×4 mesh): one reduced
arch per family × {train, prefill, decode} must lower AND compile with the
production sharding machinery.  This is the CI guard for deliverable (e);
the full 16×16 / 2×16×16 sweep runs via ``repro.launch.dryrun --all``.
"""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax
    from repro.configs import get_config, reduced
    from repro.models.config import ShapeConfig
    from repro.launch.specs import build_cell
    from repro.launch.hlo_analysis import analyze
    from repro.launch.mesh import make_mesh
    from repro.parallel.sharding import sharding_ctx

    mesh = make_mesh((2, 2, 4), ("pod", "data", "model"))
    shapes = [ShapeConfig("t", 64, 8, "train"),
              ShapeConfig("p", 64, 8, "prefill"),
              ShapeConfig("d", 64, 8, "decode")]
    archs = ["llama3.2-1b", "olmoe-1b-7b", "rwkv6-1.6b",
             "jamba-v0.1-52b", "whisper-small", "phi-3-vision-4.2b"]
    for arch in archs:
        cfg = reduced(get_config(arch))
        for sh in shapes:
            cell = build_cell(cfg, sh, mesh)
            with sharding_ctx(mesh, cell.meta.get("rules")):
                with mesh:
                    c = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                                donate_argnums=cell.donate_argnums
                                ).lower(*cell.args).compile()
            r = analyze(c.as_text())
            assert r["flops"] > 0 or sh.kind == "decode", (arch, sh.name)
            print(f"OK {arch} {sh.name} flops={r['flops']:.2e}")
    print("DRYRUN_LITE_OK")
""")


def test_dryrun_lite_multipod_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "DRYRUN_LITE_OK" in res.stdout
