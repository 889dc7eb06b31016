"""Mesh-sharded serving: tok/s scaling and bytes/device across mesh shapes.

Measures the tentpole of the sharding PR on simulated host devices
(`--xla_force_host_platform_device_count`): the slot batch shards over
the 'data' mesh axis, so each device steps B/D slots and holds 1/D of
the KV cache + serving state.

Simulated devices share one physical CPU core, so aggregate wall-clock
cannot show real scaling locally. Two things ARE real on the host and
are what this bench records:

  * per-device *step time*: the data-parallel decode burst has no
    cross-device collectives (contiguous cache; pool merges happen once
    per burst, not per step), so a device stepping B/D slots takes
    exactly the single-device time at batch B/D. `tok_s_mesh{D}` is the
    modeled aggregate B / t_step(B/D), timed on one device;
    `sharded_tok_s_scaling_4x` = t_step(B) / t_step(B/4) is gated >= 1.5
    in check_regression.py — decode compute must actually thin out per
    device, or sharding buys nothing.
  * per-device *residency*: `bytes_per_device_mesh{D}` sums the real
    shard bytes (`addressable_shards`) of the mesh scheduler's cache +
    state on one device; `sharded_bytes_per_device_shrink_4x` (gated
    >= 3.0) is the 1-device/4-device ratio — exactly 4x for the
    contiguous layout, where every leaf is slot-sharded.

Also records a token-identity check (sharded scheduler vs single-device,
greedy + sampled — the acceptance criterion the tests enforce per
family) and the replica-mode device-fit numbers: packed weights are ~32x
smaller, so under a budget set to 1/8 of the float footprint the float
deployment needs 8 devices while a whole packed replica fits on 1
(serving.replica.devices_needed, measured from real resident bytes).

On an accelerator the measurement runs in this process, over the real
devices (it needs N_DEV of them): a chip belongs to one process, and
benchmarks/run.py has already taken it. On the CPU backend it runs in a
SUBPROCESS with forced host devices: XLA_FLAGS must be set before jax
initializes, and run.py has long since imported jax by the time it
reaches this module; the parent parses the child's JSON. Either way the
result is recorded in BENCH_sharded_serving.json.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_DEV = 4


def _measure(smoke: bool) -> dict:
    """Child-process body — runs under forced host devices."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.smoke import smoke_config
    from repro.core.packed import resident_weight_bytes
    from repro.launch.mesh import make_serving_mesh
    from repro.models.api import get_model
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.replica import devices_needed

    out: dict = {"devices": len(jax.devices()), "smoke": smoke}
    assert len(jax.devices()) >= N_DEV

    # --- token identity: data=4 mesh vs single device, mixed traffic ---
    cfg = smoke_config("qwen2-72b")
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, n, dtype=np.int32),
                    max_new_tokens=m, temperature=t)
            for n, m, t in [(7, 6, 0.0), (12, 5, 0.8), (3, 8, 0.0),
                            (9, 4, 0.0)]]
    kw = dict(max_len=64, freeze=True, slots=4, kv_bits=1)
    key = jax.random.PRNGKey(7)
    want = ServingEngine(cfg, params, **kw).generate(reqs, key=key)
    got = ServingEngine(cfg, params, mesh=make_serving_mesh(N_DEV, 1),
                        **kw).generate(reqs, key=key)
    ident = all(np.array_equal(a, b) for a, b in zip(want, got))
    out["token_identical"] = bool(ident)
    assert ident, "sharded scheduler diverged from single-device tokens"

    # --- modeled per-device decode-step scaling (see module docstring) ---
    # wider than the test smoke config so compute, not per-call dispatch,
    # dominates the step (the regime sharding exists for)
    B, max_len = 16, 64
    cfg2 = smoke_config("musicgen-large").scaled(
        d_model=256, d_ff=512, head_dim=64, vocab=512, kv_bits=1)
    model2 = get_model(cfg2)
    params_f = model2.init(jax.random.PRNGKey(1))
    float_weight_bytes = sum(int(x.nbytes) for x in jax.tree.leaves(params_f))
    params2 = model2.freeze(params_f)
    step = jax.jit(model2.decode)
    reps = 3 if smoke else 10
    for d in (1, 2, 4):
        b = B // d
        cache = model2.init_cache(b, max_len)
        cur = jnp.zeros((b,), jnp.int32)
        logits, cache = step(params2, cur, cache, jnp.int32(max_len // 2))
        jax.block_until_ready(logits)          # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            logits, _ = step(params2, cur, cache, jnp.int32(max_len // 2))
            jax.block_until_ready(logits)
            best = min(best, time.perf_counter() - t0)
        out[f"t_step_mesh{d}_us"] = best * 1e6
        out[f"tok_s_mesh{d}"] = B / best
    out["sharded_tok_s_scaling_4x"] = \
        out["tok_s_mesh4"] / out["tok_s_mesh1"]

    # --- real bytes/device: mesh scheduler shards cache + state ---
    for d in (1, 2, 4):
        eng = ServingEngine(cfg2, params2, mesh=make_serving_mesh(d, 1),
                            slots=B, max_len=max_len)
        per_dev = eng.resident_bytes_per_device()
        out[f"bytes_per_device_mesh{d}"] = max(
            v["cache"] + v["state"] for v in per_dev.values())
    out["sharded_bytes_per_device_shrink_4x"] = \
        out["bytes_per_device_mesh1"] / out["bytes_per_device_mesh4"]

    # --- replica fit: the 32x shrink in device units ---
    wb = resident_weight_bytes(params2)
    packed_bytes = wb["binary"] + wb["other"]
    budget = -(-float_weight_bytes // 8)       # device holds 1/8 of float
    out["weight_bytes_float"] = float_weight_bytes
    out["weight_bytes_packed"] = packed_bytes
    out["replica_fit_float_devices"] = devices_needed(float_weight_bytes,
                                                      budget)
    out["replica_fit_packed_devices"] = devices_needed(packed_bytes, budget)
    return out


def _measure_in_child(smoke: bool) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={N_DEV}"
                        ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [os.path.join(REPO, "src"), env.get("PYTHONPATH")] if p)
    cmd = [sys.executable, os.path.abspath(__file__), "--child"]
    if smoke:
        cmd.append("--smoke")
    proc = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                          text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded-serving child failed:\n{proc.stdout}\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run(smoke: bool = False) -> list[tuple[str, float, str]]:
    import jax
    if jax.default_backend() == "cpu":
        m = _measure_in_child(smoke)
    else:
        if len(jax.devices()) < N_DEV:
            raise RuntimeError(f"sharded serving needs {N_DEV} devices, "
                               f"found {len(jax.devices())}")
        m = _measure(smoke)

    rows = [
        ("sharded_token_identity", 0.0,
         f"data={N_DEV} mesh vs single device: "
         f"{'identical' if m['token_identical'] else 'DIVERGED'}"),
    ]
    for d in (1, 2, 4):
        rows.append((f"sharded_decode_step_mesh{d}", m[f"t_step_mesh{d}_us"],
                     f"{m[f'tok_s_mesh{d}']:.1f} tok/s modeled aggregate, "
                     f"{m[f'bytes_per_device_mesh{d}'] / 1e3:.1f} KB "
                     f"cache+state/device"))
    rows += [
        ("sharded_tok_s_scaling_1to4", 0.0,
         f"{m['sharded_tok_s_scaling_4x']:.2f}x modeled tok/s "
         f"(floor 1.5; per-device step thins with the slot shard)"),
        ("sharded_bytes_per_device_1to4", 0.0,
         f"{m['sharded_bytes_per_device_shrink_4x']:.2f}x smaller "
         f"cache+state/device (floor 3.0)"),
        ("replica_device_fit", 0.0,
         f"budget=float/8: float needs {m['replica_fit_float_devices']} "
         f"devices, packed replica fits in "
         f"{m['replica_fit_packed_devices']} "
         f"({m['weight_bytes_float']} vs {m['weight_bytes_packed']} B)"),
    ]
    try:
        from benchmarks._record import record
    except ImportError:          # run as a script: benchmarks/ is sys.path[0]
        from _record import record
    record("sharded_serving", rows,
           **{k: v for k, v in m.items() if k != "smoke"}, smoke=smoke)
    return rows


if __name__ == "__main__":
    if "--child" in sys.argv:
        # XLA_FLAGS is already in our env (parent set it before spawn);
        # nothing here may import jax before this point
        print(json.dumps(_measure(smoke="--smoke" in sys.argv)))
    else:
        print("name,us_per_call,derived")
        for name, us, derived in run(smoke="--smoke" in sys.argv):
            print(f"{name},{us:.1f},{derived}")
