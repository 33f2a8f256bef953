"""Chip smoke test: the serving path at published widths on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # 4-way tensor parallel vs one device

Default run, on one chip:

1. device: JAX must see a TPU, and the kernel dispatch must pick compiled
   Pallas (not interpret mode, no ``REPRO_FORCE_*`` override);
2. llama3.2-1b (16 layers, d_model 2048) served through
   ``repro.launch.serve.main`` with random weights from the seed: every
   request finishes ok, no admission bypasses the chunked path, and the
   greedy tokens of the first prompts match a plain loop outside the
   engine (the admission forward, one masked extend into a fresh cache,
   then ``decode_step``) on the first token. In float32, the admission
   forward and ``prefill`` give first-position logits that are finite and
   agree within ``LOGIT_TOL``;
3. mamba2-780m (48 layers, d_model 1536), the same checks. Its admission
   runs the ``ssd_extend`` Pallas kernels and its prefill the ``ssd``
   kernel; both are also compared with the jnp reference at these widths
   within ``KERNEL_TOL``, ``ssd_extend`` in its chunked form and in its
   token-serial one.

``--four-chips`` runs only the tensor-parallel phase: llama3.2-1b served
with ``--mesh 1,4`` and the same requests on one device, in this
process. Greedy tokens must agree on the first token of every request,
prefill logits within ``TP_LOGIT_TOL``, and params and KV cache must be
spread over all four devices.

One process, no children. Any failed check exits non-zero before the
last line, which is the JSON result
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import functools
import importlib.metadata
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.model import build  # noqa: E402

LLAMA, MAMBA = "llama3.2-1b", "mamba2-780m"
VARIANT = ""           # the published config
SEED = 0
REQUESTS, MAX_BATCH, CACHE_LEN, PROMPT_LEN, MAX_NEW = 8, 8, 1024, 256, 32
N_ORACLE = 2           # prompts replayed through the plain prefill/decode loop
# max |a - b| over the vocab, relative to max |b|, for first-position
# logits of two float32 forwards (HIGHEST matmuls) that differ in
# algorithm: chunked vs sequential scan, causal prefill vs masked extend
LOGIT_TOL = 1e-2
TP_LOGIT_TOL = 0.05
# Pallas SSD kernels vs the float32 jnp reference at mamba2-780m widths,
# relative to max |reference|
KERNEL_TOL = 2e-2
SERIAL_T = 8        # ssd_extend tokens below the chunked form's block
FORCE_ENVS = ("REPRO_FORCE_REF", "REPRO_FORCE_PALLAS")
_T0 = time.perf_counter()


def log(msg):
    """A phase header, with seconds since start (compiles included)."""
    print(f"[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _version(pkg):
    try:
        return importlib.metadata.version(pkg)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def device_phase(n_chips):
    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu",
          f"no TPU: JAX found {len(devs)} {d0.platform} device(s); this "
          f"check runs only on the chip")
    print(f"device: kind={d0.device_kind} count={len(devs)} "
          f"jax={jax.__version__} jaxlib={_version('jaxlib')} "
          f"libtpu={_version('libtpu')}")
    check(len(devs) >= n_chips, f"need {n_chips} chips, found {len(devs)}")
    forced = [e for e in FORCE_ENVS if os.environ.get(e)]
    check(not forced, f"kernel choice overridden by {forced}")
    choice = dispatch.resolve()
    print(f"kernels: dispatch.resolve() = (use_pallas, interpret) = "
          f"{choice}")
    check(choice == (True, False),
          f"expected compiled Pallas (True, False), got {choice}")
    return d0


def serve_phase(arch, extra=()):
    argv = ["--arch", arch, "--variant", VARIANT,
            "--requests", str(REQUESTS),
            "--max-batch", str(MAX_BATCH), "--cache-len", str(CACHE_LEN),
            "--prompt-len", str(PROMPT_LEN), "--max-new", str(MAX_NEW),
            "--temperature", "0", "--seed", str(SEED), *extra]
    log(f"serve {' '.join(argv)}")
    responses, stats = serve.main(argv)
    bad = sorted(u for u, r in responses.items() if not (r.ok and r.tokens))
    check(len(responses) == REQUESTS and not bad,
          f"{arch}: {len(responses)}/{REQUESTS} responses, not ok: {bad}")
    check(stats["fallback_admissions"] == 0,
          f"{arch}: {stats['fallback_admissions']} fallback admissions")
    print(f"{arch}: {REQUESTS} responses ok, fallback_admissions=0")
    return responses, stats


def _rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    scale = float(np.max(np.abs(b)))
    return float(np.max(np.abs(a - b))) / max(scale, 1e-30), scale


def _agree(a, b):
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


def _padded(prompt):
    """A prompt as the engine admits it: right-padded to the chunk length,
    with its valid length."""
    tokens = np.zeros((1, CACHE_LEN), np.int32)
    tokens[0, :len(prompt)] = prompt
    return jnp.asarray(tokens), jnp.asarray([len(prompt)], jnp.int32)


def _forwards(model):
    """Jitted first-position forwards of one padded prompt: the engine's
    admission (one masked extend into a fresh cache; also returns the
    cache) and ``prefill``."""
    def admit(params, tokens, length):
        logits, cache = model.extend_into_cache(
            params, tokens, model.make_cache(1, CACHE_LEN), length,
            last_only=True)
        return logits[0, -1], cache

    def prefill(params, tokens, length):
        logits, _ = model.prefill(params, {"tokens": tokens,
                                           "length": length},
                                  model.make_cache(1, CACHE_LEN))
        return logits[0, -1]
    return jax.jit(admit), jax.jit(prefill)


def oracle_phase(arch, responses):
    """The engine's greedy tokens for the first prompts against a plain
    loop outside the engine: one request, its admission forward, then
    ``decode_step``, in the served dtype. Then the two forwards a prompt
    can take, ``prefill`` (chunked SSD scan / causal attention) and the
    admission's extend, must give the same first-position logits in
    float32 at HIGHEST matmul precision.

    That comparison is made in float32 because these random-weight bf16
    stacks amplify rounding at published width: on the chip, bf16
    forwards of mamba2-780m that differ only in op order disagree by up
    to a third of max |logit| and pick different tokens."""
    cfg = get_arch(arch, variant=VARIANT)
    prompts = [_padded(p) for p, _ in
               serve.synthetic_prompts(cfg, REQUESTS, PROMPT_LEN, SEED)]
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    admit, _ = _forwards(model)
    step = jax.jit(model.decode_step)
    log(f"{arch}: plain admission + decode loop")
    for uid in range(N_ORACLE):
        first, cache = admit(params, *prompts[uid])
        check(np.isfinite(np.asarray(first, np.float32)).all(),
              f"{arch} uid={uid}: non-finite first-position logits")
        got = list(responses[uid].tokens)
        ref = [int(jnp.argmax(first))]
        while len(ref) < len(got):
            lo, cache = step(params, jnp.asarray([[ref[-1]]], jnp.int32),
                             cache)
            ref.append(int(jnp.argmax(lo[0, -1])))
        print(f"{arch} uid={uid} prompt_len={int(prompts[uid][1][0])}: "
              f"greedy tokens agree {_agree(got, ref)}/{len(got)} (engine "
              f"first={got[0]} oracle first={ref[0]})")
        check(got[0] == ref[0], f"{arch} uid={uid}: first token differs")
    del params, cache

    log(f"{arch}: prefill vs admission forward, float32")
    model = build(cfg.replace(dtype="float32", param_dtype="float32"))
    params = model.init(jax.random.PRNGKey(SEED))
    admit, prefill = _forwards(model)
    for uid in range(N_ORACLE):
        with jax.default_matmul_precision("highest"):
            ext, _ = admit(params, *prompts[uid])
            first = prefill(params, *prompts[uid])
        ext, first = np.asarray(ext), np.asarray(first)
        check(np.isfinite(ext).all() and np.isfinite(first).all(),
              f"{arch} uid={uid}: non-finite float32 logits")
        err, scale = _rel_err(ext, first)
        print(f"{arch} uid={uid}: float32 first-position logits extend vs "
              f"prefill max|diff|/max|logit|={err:.6g} (max|logit|="
              f"{scale:.6g}, tol {LOGIT_TOL}); argmax {int(ext.argmax())} "
              f"vs {int(first.argmax())}")
        check(err <= LOGIT_TOL, f"{arch} uid={uid}: logits differ by {err}")


def ssd_kernel_phase(cfg):
    """ssd / ssd_extend compiled Pallas vs the jnp reference (float32
    matmuls) at the config's widths, each case named by the Pallas call
    its program must hold. ``ssd_extend`` is checked in both of its
    forms: at ``L`` tokens (a multiple of the block) it runs the chunked
    dual form, at ``SERIAL_T`` the token-serial kernel."""
    from repro.kernels.ssd_scan import ops as ssd_ops
    log("SSD kernels vs reference")
    s = cfg.ssm
    h, p, n, g = s.n_heads(cfg.d_model), s.head_dim, s.d_state, s.n_groups
    L = CACHE_LEN
    ks = jax.random.split(jax.random.PRNGKey(SEED), 6)
    x = jax.random.normal(ks[0], (1, L, h, p), jnp.float32)
    dt = jax.random.uniform(ks[1], (1, L, h), jnp.float32, 1e-3, 0.1)
    A = -jnp.exp(jnp.log(jnp.linspace(1.0, 16.0, h)))
    B = jax.random.normal(ks[2], (1, L, g, n), jnp.float32)
    C = jax.random.normal(ks[3], (1, L, g, n), jnp.float32)
    D = jnp.ones((h,), jnp.float32)
    s0 = 0.1 * jax.random.normal(ks[4], (1, h, p, n), jnp.float32)
    head = lambda a: a[:, :SERIAL_T]
    cases = {
        ("ssd_scan", L): (functools.partial(ssd_ops.ssd, chunk=s.chunk),
                          (x, dt, A, B, C, D)),
        ("ssd_extend_chunked", L): (ssd_ops.ssd_extend,
                                    (s0, x, dt, A, B, C, D)),
        ("ssd_extend", SERIAL_T): (
            ssd_ops.ssd_extend,
            (s0, head(x), head(dt), A, head(B), head(C), D)),
    }
    for (name, T), (fn, args) in cases.items():
        pallas = jax.jit(functools.partial(fn, use_pallas=True))
        y, st = pallas(*args)
        with jax.default_matmul_precision("highest"):
            yr, sr = jax.jit(functools.partial(fn, use_pallas=False))(*args)
        ey, _ = _rel_err(y, yr)
        es, _ = _rel_err(st, sr)
        print(f"{name} Pallas vs jnp reference at "
              f"L={T} h={h} p={p} n={n}: y {ey:.6g}, state {es:.6g} "
              f"(max|diff|/max|ref|, tol {KERNEL_TOL})")
        check(max(ey, es) <= KERNEL_TOL, f"{name}: Pallas vs reference")
        hlo = pallas.lower(*args).compile().as_text()
        check("tpu_custom_call" in hlo,
              f"{name}: no compiled Pallas kernel in the program")
        check(f"%{name}." in hlo, f"{name}: the program runs another form")


def four_chip_phase():
    """llama3.2-1b served 4-way tensor parallel and on one device, the
    same requests in this process. Params and KV cache must be spread
    over the four devices; every prompt's first-position admission logits
    must agree within ``TP_LOGIT_TOL``; and the two servers' first tokens
    must agree wherever the one-device logits' top two are further apart
    than twice the measured TP vs one-device difference (closer than
    that, either token is within the logits' agreement)."""
    from repro.distribution import sharding as SH
    from repro.launch.mesh import make_serving_mesh

    tp, tp_stats = serve_phase(LLAMA, ["--mesh", "1,4"])
    one, _ = serve_phase(LLAMA, ["--mesh", "none"])
    for what in ("param", "cache"):
        by_dev = tp_stats[f"{what}_bytes_by_device"]
        total = sum(by_dev.values())
        print(f"4-way TP {what} bytes by device: {by_dev} (total {total})")
        check(len(by_dev) == 4 and min(by_dev.values()) >= total / 8,
              f"{what}s not spread over 4 devices: {by_dev}")
    agree = [_agree(tp[u].tokens, one[u].tokens) for u in range(REQUESTS)]
    print(f"greedy tokens agree (4-way TP vs one device), per request: "
          f"{agree} of {MAX_NEW}")

    cfg = get_arch(LLAMA, variant=VARIANT)
    prompts = [_padded(p) for p, _ in
               serve.synthetic_prompts(cfg, REQUESTS, PROMPT_LEN, SEED)]
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    mesh = make_serving_mesh("1,4")
    params_tp = jax.device_put(params, SH.param_shardings(params, mesh))
    rules = SH.default_activation_rules()
    with SH.activation_sharding(mesh, rules):
        print(f"kernels under 4-way TP: dispatch.resolve() = "
              f"{dispatch.resolve()} (Pallas ops take the jnp reference "
              f"under a model axis > 1, by design)")
    # separate jits, so neither reuses the other's trace
    (admit_tp, _), (admit_one, _) = _forwards(model), _forwards(model)
    for uid in range(REQUESTS):
        with SH.activation_sharding(mesh, rules):
            a, _ = admit_tp(params_tp, *prompts[uid])
        b, _ = admit_one(params, *prompts[uid])
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        check(np.isfinite(a).all(), f"uid={uid}: non-finite TP logits")
        err, scale = _rel_err(a, b)
        second, top = np.sort(b)[-2:]
        tie = top - second <= 2 * err * scale
        print(f"uid={uid}: admission logits 4-way TP vs one device "
              f"max|diff|/max|logit|={err:.6g} (max|logit|={scale:.6g}, "
              f"tol {TP_LOGIT_TOL}); first token {tp[uid].tokens[0]} vs "
              f"{one[uid].tokens[0]}; one-device top-two gap "
              f"{top - second:.6g}{' (within the difference)' if tie else ''}")
        check(err <= TP_LOGIT_TOL, f"uid={uid}: TP logits differ by {err}")
        check(tp[uid].tokens[0] == one[uid].tokens[0] or tie,
              f"uid={uid}: first token differs under TP")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-way tensor-parallel phase")
    args = ap.parse_args(argv)
    enable_compile_cache()
    try:
        d0 = device_phase(4 if args.four_chips else 1)
        if args.four_chips:
            four_chip_phase()
        else:
            responses, _ = serve_phase(LLAMA)
            oracle_phase(LLAMA, responses)
            responses, _ = serve_phase(MAMBA)
            oracle_phase(MAMBA, responses)
            ssd_kernel_phase(get_arch(MAMBA, variant=VARIANT))
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    log("all checks passed")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
