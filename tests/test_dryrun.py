"""Dry-run machinery: small-mesh lower+compile in a subprocess (the forced
device count must land before jax init), plus the HLO cost model."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code, n_devices=8):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}",
               PYTHONPATH=SRC)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=540)


@pytest.mark.slow
def test_small_mesh_compile_train_and_decode():
    code = """
import jax
from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.launch.specs import build_case
mesh = make_test_mesh((2, 2), ("data", "model"))
for arch in ("qwen2-1.5b", "xlstm-125m"):
    cfg = get_config(arch).reduced()
    import dataclasses
    cfg = dataclasses.replace(cfg, name=cfg.name)
    for shape in ("train_4k", "decode_32k"):
        case = build_case(cfg, shape, mesh)
        with jax.set_mesh(mesh):
            c = jax.jit(case.step_fn,
                        in_shardings=case.in_shardings
                        ).lower(*case.args).compile()
        assert c.memory_analysis() is not None
        print("OK", arch, shape)
"""
    r = _run(code)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.count("OK") == 4


def test_hlo_cost_model_exact_on_known_program():
    code = """
import jax, jax.numpy as jnp
from jax import lax
from repro.analysis.hlo_cost import analyze_text
def f(a, bs):
    def body(c, b):
        return c, a @ b
    _, ys = lax.scan(body, None, bs)
    return ys
a = jnp.zeros((64, 128), jnp.float32)
bs = jnp.zeros((5, 128, 256), jnp.float32)
c = jax.jit(f).lower(a, bs).compile()
r = analyze_text(c.as_text())
expect = 5 * 2 * 64 * 128 * 256
assert abs(r["flops"] - expect) / expect < 1e-6, r["flops"]
print("COST_OK")
"""
    r = _run(code, n_devices=1)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "COST_OK" in r.stdout


def test_collective_parse():
    from repro.analysis.hlo_cost import analyze_text
    hlo = """
HloModule test

ENTRY %main (p: f32[16,16]) -> f32[16,16] {
  %p = f32[16,16]{1,0} parameter(0)
  %ag = f32[32,16]{1,0} all-gather(%p), dimensions={0}
  %slice = f32[16,16]{1,0} slice(%ag), slice={[0:16], [0:16]}
  ROOT %ar = f32[16,16]{1,0} all-reduce(%slice), to_apply=%add
}
"""
    r = analyze_text(hlo)
    assert r["collectives"]["all-gather"] == 32 * 16 * 4
    assert r["collectives"]["all-reduce"] == 16 * 16 * 4
