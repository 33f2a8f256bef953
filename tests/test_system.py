"""End-to-end behaviour tests for the paper's system: train a backbone,
wrap it as a zoo service, compose, publish, pull, deploy, serve —
the full Zoo lifecycle on one reduced model."""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]


def test_full_zoo_lifecycle(tmp_path):
    import repro.core.zoo_builders as zb
    from repro.configs import get_arch
    from repro.core.deploy import DeploymentPlan, deploy
    from repro.core.registry import Registry
    from repro.data.pipeline import batches_for
    from repro.models.model import build
    from repro.training.optimizer import AdamW, cosine_schedule
    from repro.training.train_loop import train

    # 1. train (briefly) — loss must move
    cfg = get_arch("llama3.2-1b", variant="reduced")
    model = build(cfg)
    opt = AdamW(lr=cosine_schedule(3e-3, 5, 40))
    state, hist = train(model, opt, batches_for(cfg, 8, 48), steps=40,
                        log_every=39)
    assert hist[-1]["loss"] < hist[0]["loss"]

    # 2. wrap as a service with the trained params, publish
    svc = zb.lm_service("llama3.2-1b", variant="reduced").with_params(
        state["params"])
    reg = Registry(tmp_path)
    reg.publish(svc, builder="model.lm",
                config={"arch": "llama3.2-1b", "variant": "reduced"})

    # 3. pull and verify identical behaviour
    pulled = reg.pull(svc.name)
    x = {"tokens": jnp.ones((2, 16), jnp.int32)}
    np.testing.assert_allclose(np.asarray(svc(x)), np.asarray(pulled(x)),
                               rtol=1e-5, atol=1e-5)

    # 4. deploy the pulled service locally and call it
    d = deploy(pulled, DeploymentPlan.all_local(pulled))
    out, tel = d.call(x)
    assert out.shape == (2, 16, cfg.vocab)
    assert tel.total_s > 0

    # 5. serve generation with the trained weights
    from repro.serving.engine import Engine
    from repro.serving.request import Request
    eng = Engine(model, state["params"], max_batch=2, cache_len=64)
    eng.submit(Request(uid=0, prompt=np.asarray([1, 2, 3]),
                       max_new_tokens=5))
    resp = eng.run()
    assert resp[0].finished and resp[0].n_generated == 5


def test_dryrun_small_mesh_subprocess():
    """Multi-device lower+compile in a subprocess (8 fake devices) —
    validates the sharding rules end-to-end without the 512-device cost."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.configs import SHAPES
from repro.launch.steps import build_step, activation_rules_for
from repro.distribution.sharding import activation_sharding

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
for arch, shape in [("llama3.2-1b", "decode_32k"),
                    ("qwen2-moe-a2.7b", "train_4k"),
                    ("mamba2-780m", "prefill_32k")]:
    step_fn, args, cfg, info = build_step(arch, shape, mesh)
    rules = activation_rules_for(mesh, SHAPES[shape])
    with mesh, activation_sharding(mesh, rules):
        compiled = jax.jit(step_fn).lower(*args).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    assert ca["flops"] > 0
    print("OK", arch, shape)
"""
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.count("OK") == 3


def test_shape_skip_table_matches_design():
    """The only skipped (arch x shape) pair is the documented one."""
    from repro.configs import ARCHS, SHAPES
    from repro.launch.steps import ShapeSkip, resolve_config
    skips = []
    for arch in sorted(ARCHS):
        for shape in sorted(SHAPES):
            try:
                resolve_config(arch, shape)
            except ShapeSkip:
                skips.append((arch, shape))
    assert skips == [("seamless-m4t-medium", "long_500k")]
