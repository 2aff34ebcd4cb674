#!/usr/bin/env python3
"""Run the device path once on one TPU and check it against the host.

    python3 chip_smoke.py

One process, one chip. The phases run in order, and any error or
mismatch raises, so the exit code is non-zero:

device      the first device JAX finds must be a TPU; nothing runs on
            the CPU in its place.
fig2        the paper's Fig. 2 scenario (J=9 proxies, N=1e6 objects,
            B=30,000 units) through ``Scenario.run()`` on the compiled
            XLA driver, against the same scenario on the host engine
            (``backend="c"``, or ``"flat"`` without a C compiler): the
            two Reports must be equal, and the driver's state must live
            on the TPU.
table1      the Table-I scenario as an 8-lane ensemble in one compiled
            program; every lane must equal the sequential host run of
            its seed, bit for bit.
workingset  the working-set fixed point (float32), one
            ``solve_workingset`` call per allocation, for the 8 Table-II
            allocations and two at Fig-2 size, on the TPU and on the
            host's CPU device in this process; every solve must
            converge and ``h`` must agree within ``WS_H_ATOL``. Each
            device's float32 ``expm1``/``exp`` error at the solver's
            arguments is printed beside the gap.
kernels     paged and shared-prefix decode attention at qwen3-1.7b KV
            widths against ``kernels/ref.py``; each executable must hold
            a Mosaic kernel (``tpu_custom_call``).

Lines before the last are smoke timings on the host clock, with
compilation kept apart: they are not benchmark numbers. The last line is
one JSON object naming the device.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# Requests driven through the compiled loops. The device runs one
# predicated loop step per request, so the paper's trace lengths (3M for
# Fig. 2, 10M for Table I) are cut to what the chip drives in about a
# minute; every width (J, N, allocations, Zipf exponents) stays.
FIG2_REQUESTS = 750_000
TABLE1_REQUESTS = 1_000_000
TABLE1_LANES = 8

# h is a probability computed in float32. On the host the float32 solve
# is within 3.6e-7 of the float64 one at Fig-2 size and 1.6e-7 on the
# Table-II grid. A TPU v5e's float32 exp and expm1 are less exact: the
# workingset phase measures both devices' errors at the solver's own
# arguments and prints them beside the h gap. On a TPU v5e, h = -expm1(x)
# is off by up to 3.6e-6 at a fixed t (host: 1.1e-7), and the fixed
# point carries that to a gap of up to 8.5e-6. 1e-5 bounds that and
# stays 1000x below the 1e-2 gaps between attribution models that the
# solver is used to tell apart.
WS_H_ATOL = 1e-5

# qwen3-1.7b KV geometry, and the decode batch: P prefixes of S tokens,
# M requests per prefix, each with a suffix of up to SS tokens, paged in
# blocks of PAGE tokens.
H, KV, D = 16, 8, 128
P, M, S, SS, PAGE = 4, 8, 4096, 256, 16
BF16_ATOL = 2e-2  # the bf16 tolerance of tests/test_kernels.py


def log(msg: str) -> None:
    print(msg, flush=True)


def expect_equal(got, want, what: str) -> None:
    if got != want:
        raise AssertionError(f"{what}: {got!r} != {want!r}")


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's
    monitoring events."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Phase:
    """Host-clock wall time of one phase, with the compile time spent in
    it kept apart."""

    def __init__(self, name: str, compiles: CompileLog, device) -> None:
        self.name, self.compiles, self.device = name, compiles, device

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.compiles.seconds
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            wall = time.perf_counter() - self.t0
            comp = self.compiles.seconds - self.c0
            stats = self.device.memory_stats() or {}
            log(
                f"smoke timing: phase={self.name} wall_s={wall:.3f} "
                f"compile_s={comp:.3f} "
                f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}"
            )


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"no TPU found: JAX's first device is {dev.platform!r} "
            f"({dev.device_kind}); this smoke test runs on a TPU only"
        )
    log(
        f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())}"
    )
    return dev


def host_backend() -> str:
    from repro.core import fastsim_c

    return "c" if fastsim_c.available() else "flat"


def with_backend(sc, backend: str, **estimator):
    sc = dataclasses.replace(
        sc, system=dataclasses.replace(sc.system, backend=backend)
    )
    if estimator:
        sc = dataclasses.replace(
            sc, estimator=dataclasses.replace(sc.estimator, **estimator)
        )
    return sc


def cut_requests(sc, n: int):
    if n == sc.n_requests:
        return sc
    log(f"reduced: n_requests={n} of {sc.n_requests} ({sc.name})")
    out = sc.scaled(requests=n / sc.n_requests)
    expect_equal(out.n_requests, n, "cut n_requests")
    return out


def expect_same_report(dev_rep, host_rep, what: str) -> None:
    np.testing.assert_array_equal(
        dev_rep.hit_rate, host_rep.hit_rate, err_msg=f"{what} hit_rate"
    )
    np.testing.assert_array_equal(
        dev_rep.realized_hit_rate,
        host_rep.realized_hit_rate,
        err_msg=f"{what} realized_hit_rate",
    )
    np.testing.assert_array_equal(
        dev_rep.final_vlen, host_rep.final_vlen, err_msg=f"{what} final_vlen"
    )
    expect_equal(dev_rep.ripple, host_rep.ripple, f"{what} ripple histogram")
    for key in ("n_hit_list", "n_hit_cache", "n_miss"):
        expect_equal(dev_rep.extras[key], host_rep.extras[key], f"{what} {key}")
    # the Fig-2 state (J*N = 9M) always streams: sparse occupancy
    for part in ("indices", "values"):
        np.testing.assert_array_equal(
            getattr(dev_rep.hit_prob, part),
            getattr(host_rep.hit_prob, part),
            err_msg=f"{what} hit_prob {part}",
        )


def fig2_phase(dev, n_requests: int) -> None:
    from repro.core.fastsim_jax import XLAChunkRunner
    from repro.core.shared_lru import _lcm_1_to
    from repro.scenario import get_preset

    sc = cut_requests(get_preset("fig2_ripple"), n_requests)
    t0 = time.perf_counter()
    rep = with_backend(sc, "xla").run()
    wall = time.perf_counter() - t0
    expect_equal(rep.backend, "xla", "fig2 engine that ran")
    log(
        f"fig2: xla streamed={rep.extras['streaming']} "
        f"n_requests={rep.n_requests} wall_s={wall:.3f} "
        f"drive_s={rep.elapsed_s:.3f} "
        f"requests_per_s={rep.throughput_rps:.1f} "
        f"hit_rate={rep.overall_hit_rate:.6f}"
    )
    backend = host_backend()
    t0 = time.perf_counter()
    host = with_backend(sc, backend).run()
    log(
        f"fig2: host backend={host.backend} "
        f"wall_s={time.perf_counter() - t0:.3f} drive_s={host.elapsed_s:.3f}"
    )
    expect_same_report(rep, host, "fig2 xla vs host")
    log(
        f"fig2: xla == {host.backend}: hit_rate, ripple histogram, "
        "n_hit_list/n_hit_cache/n_miss, final_vlen and hit_prob equal"
    )

    # the driver's carried state is allocated on the TPU itself
    system, wl = sc.system, sc.workload
    runner = XLAChunkRunner(
        system.to_sim_params(),
        wl.n_objects,
        np.ones(wl.n_objects, dtype=np.int64),
        sc.warmup,
        sc.warmup,
        _lcm_1_to(len(system.allocations)),
    )
    held = {d for leaf in jax.tree_util.tree_leaves(runner.st) for d in leaf.devices()}
    expect_equal(held, {dev}, "devices holding the XLA driver state")
    log(f"fig2: XLAChunkRunner state on {dev.device_kind}")
    del runner


def table1_phase(n_requests: int, lanes: int) -> None:
    from repro.scenario import get_preset

    sc = cut_requests(get_preset("table1"), n_requests)
    t0 = time.perf_counter()
    rep = with_backend(sc, "xla", replications=lanes).run()
    wall = time.perf_counter() - t0
    expect_equal(rep.backend, "xla", "table1 engine that ran")
    expect_equal(rep.ensemble["batched"], True, "table1 lanes in one program")
    log(
        f"table1: xla lanes={lanes} n_requests={rep.n_requests} "
        f"wall_s={wall:.3f} drive_s={rep.elapsed_s:.3f} "
        f"requests_per_s={rep.throughput_rps:.1f}"
    )
    backend = host_backend()
    t0 = time.perf_counter()
    host = with_backend(sc, backend, replications=lanes).run()
    log(
        f"table1: host backend={host.backend} sequential "
        f"wall_s={time.perf_counter() - t0:.3f} drive_s={host.elapsed_s:.3f}"
    )
    expect_equal(host.ensemble["batched"], False, "table1 host sequential")
    for key in ("hit_prob", "hit_rate", "realized_hit_rate", "overall_hit_rate"):
        np.testing.assert_array_equal(
            rep.ensemble[key], host.ensemble[key], err_msg=f"table1 {key}"
        )
    expect_equal(rep.ripple, host.ripple, "table1 ripple histogram")
    for key in ("n_hit_list", "n_hit_cache", "n_miss"):
        expect_equal(rep.extras[key], host.extras[key], f"table1 {key}")
    log(
        f"table1: each of {lanes} xla lanes == sequential {host.backend} run "
        "of its seed: per-lane hit_prob, hit rates; summed counters and "
        "ripple histogram equal"
    )


def float32_exp_errors(x, device) -> dict:
    """Errors of float32 ``expm1`` and ``exp`` computed on ``device`` at
    the arguments ``x``, against float64 numpy at the same float32
    values: the absolute error of ``expm1`` is the error of
    ``h = -expm1(-lam t)`` at a fixed ``t``. Relative errors count only
    results in float32's normal range, where ``exp`` has not underflowed."""
    x = jax.device_put(np.asarray(x, np.float32), device)
    x64 = np.asarray(x, np.float64)
    errs = {}
    for name, fn, ref in (("expm1", jnp.expm1, np.expm1), ("exp", jnp.exp, np.exp)):
        got = np.asarray(jax.jit(fn)(x), np.float64)
        want = ref(x64)
        normal = np.abs(want) >= np.finfo(np.float32).tiny
        rel = np.abs(got[normal] - want[normal]) / np.abs(want[normal])
        errs[f"{name}_rel"] = float(rel.max())
        if name == "expm1":
            errs["expm1_abs"] = float(np.max(np.abs(got - want)))
    return errs


def workingset_phase(dev) -> None:
    from repro.core.workingset import solve_workingset
    from repro.scenario import get_preset
    from repro.scenario.presets import FIG2_B_UNITS

    table2_grid = [
        (b0, b1, b2) for b0 in (8, 64) for b1 in (8, 64) for b2 in (8, 64)
    ]
    cases = (
        ("table2", get_preset("table1").workload, table2_grid),
        (
            "fig2",
            get_preset("fig2_ripple").workload,
            [FIG2_B_UNITS, tuple(2 * b for b in FIG2_B_UNITS)],
        ),
    )
    cpu = jax.devices("cpu")[0]
    for name, wl, grid in cases:
        lam = wl.rates()
        lengths = np.ones(wl.n_objects)
        grid = np.asarray(grid, dtype=np.float64)
        sols = {}
        for where, device in (("tpu", dev), ("host_cpu", cpu)):
            t0 = time.perf_counter()
            with jax.default_device(device):
                sols[where] = [solve_workingset(lam, lengths, b) for b in grid]
            log(
                f"workingset {name}: {where} solves={len(grid)} "
                f"wall_s={time.perf_counter() - t0:.3f} "
                f"iterations={[s.iterations for s in sols[where]]} "
                f"converged={[s.converged for s in sols[where]]}"
            )
            if not all(s.converged for s in sols[where]):
                raise AssertionError(f"workingset {name}: {where} did not converge")
        # the arguments of h = -expm1(-lam t) at the host's first root
        x = -lam * sols["host_cpu"][0].t[:, None]
        for where, device in (("tpu", dev), ("host_cpu", cpu)):
            errs = float32_exp_errors(x, device)
            log(
                f"workingset {name}: {where} float32 at the solver's arguments: "
                + " ".join(f"{k}={v:.3e}" for k, v in errs.items())
            )
        gap = max(
            float(np.max(np.abs(a.h - b.h)))
            for a, b in zip(sols["tpu"], sols["host_cpu"])
        )
        log(f"workingset {name}: max|h_tpu - h_host| = {gap:.3e} (atol {WS_H_ATOL})")
        if not gap <= WS_H_ATOL:
            raise AssertionError(
                f"workingset {name}: chip drifts from host by {gap:.3e}"
            )


def _decode_inputs(key):
    """A shared-prefix decode batch and the same batch as a paged pool:
    each prefix's pages are stored once and every request of the group
    points its block table at them, followed by its own suffix pages."""
    ks = jax.random.split(key, 6)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (P, M, H, D), bf)
    pk = jax.random.normal(ks[1], (P, S, KV, D), bf)
    pv = jax.random.normal(ks[2], (P, S, KV, D), bf)
    sk = jax.random.normal(ks[3], (P, M, SS, KV, D), bf)
    sv = jax.random.normal(ks[4], (P, M, SS, KV, D), bf)
    plens = jnp.full((P,), S, jnp.int32)
    slens = jax.random.randint(ks[5], (P, M), 1, SS + 1, jnp.int32)

    def pool(prefix, suffix):
        a = prefix.reshape(P * S // PAGE, PAGE, KV, D)
        b = suffix.reshape(P * M * SS // PAGE, PAGE, KV, D)
        return jnp.moveaxis(jnp.concatenate([a, b]), 2, 0)

    n_pre, n_suf = S // PAGE, SS // PAGE
    p_idx = np.arange(P)[:, None, None]
    r_idx = np.arange(P * M).reshape(P, M)[..., None]
    tables = np.concatenate(
        [
            np.broadcast_to(p_idx * n_pre + np.arange(n_pre), (P, M, n_pre)),
            P * n_pre + r_idx * n_suf + np.arange(n_suf),
        ],
        axis=2,
    ).reshape(P * M, n_pre + n_suf)
    decode = (q, pk, pv, plens, sk, sv, slens)
    paged = (
        q.reshape(P * M, H, D),
        pool(pk, sk),
        pool(pv, sv),
        jnp.asarray(tables, jnp.int32),
        (S + slens).reshape(P * M),
    )
    return decode, paged


def _run_kernel(name, fn, args):
    """Compile ``fn`` for the chip, check that a Mosaic kernel is in the
    executable, and run that same executable."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args, interpret=False).compile()
    compile_s = time.perf_counter() - t0
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{name}: no tpu_custom_call in the executable")
    out = compiled(*args)
    out.block_until_ready()
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        out = compiled(*args)
    out.block_until_ready()
    run_s = (time.perf_counter() - t0) / reps
    log(
        f"kernels {name}: tpu_custom_call present compile_s={compile_s:.3f} "
        f"run_s={run_s:.6f}"
    )
    return out


def kernels_phase(seed: int = 0) -> None:
    from repro.kernels import ops, ref

    decode, paged = _decode_inputs(jax.random.PRNGKey(seed))
    got_paged = _run_kernel("paged_attention", ops.paged_attention, paged)
    got_decode = _run_kernel(
        "shared_prefix_decode", ops.shared_prefix_decode, decode
    )
    with jax.default_matmul_precision("highest"):
        want_paged = ref.reference_paged_attention(*paged)
        want_decode = ref.reference_shared_prefix_decode(*decode)
    for name, got, want in (
        ("paged_attention", got_paged, want_paged),
        ("shared_prefix_decode", got_decode, want_decode),
    ):
        err = float(
            jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))
        )
        log(f"kernels {name}: max|kernel - ref| = {err:.3e} (atol {BF16_ATOL})")
        if not err <= BF16_ATOL:
            raise AssertionError(f"{name} differs from its reference by {err}")


def main() -> None:
    dev = require_tpu()
    from repro.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    compiles = CompileLog()
    with Phase("fig2", compiles, dev):
        fig2_phase(dev, FIG2_REQUESTS)
    with Phase("table1", compiles, dev):
        table1_phase(TABLE1_REQUESTS, TABLE1_LANES)
    with Phase("workingset", compiles, dev):
        workingset_phase(dev)
    with Phase("kernels", compiles, dev):
        kernels_phase()
    log(
        f"compile cache: hits={compiles.hits} misses={compiles.misses} "
        f"backend_compile_s={compiles.seconds:.3f}"
    )
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(jax.devices()),
                },
            }
        )
    )


if __name__ == "__main__":
    main()
