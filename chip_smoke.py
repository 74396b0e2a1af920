"""Chip smoke: drive ``repro.diversify()`` end to end on a TPU and check
every result against a plain reference (numpy float64, or jnp at full f32
matmul precision), independent of the engine.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: MapReduce over a mesh only

One chip, all data from ``--seed``:

* ``sphere``      batch, paper sphere: n=2^26, d=3, k=32, kprime=128
* ``catalog``     batch, cosine: 2^20 Gaussian rows x 768, k=64, once on the
                  jnp sweep and once on the compiled Pallas kernels
* ``constrained`` batch, 2^24 sphere points in 16 Zipf-sized groups,
                  quotas summing to k=32 (the grouped engine)
* ``streaming``   64 chunks of 2^20 x 3 from a seeded generator
* ``mapreduce``   the 2^26 sphere on a one-device ``Mesh`` (shard_map) and
                  with ``num_reducers=4`` (simulated)

Four chips (``--chips 4``): 2^28 sphere points generated already sharded
over a 4-device mesh, run through shard_map, and compared with
``num_reducers=4`` simulated on one chip at the same n.

Every check prints one line.  The last stdout line is
``{"ok": true, "device": {...}}`` only when every check passed; with no
TPU (or without the ``src/`` tree next to this file) the script exits
non-zero and prints no such line.  One process holds the chip throughout.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

FULL = dict(sphere_n=1 << 26, catalog_n=1 << 20, catalog_d=768,
            grouped_n=1 << 24, stream_chunks=64, stream_chunk=1 << 20,
            mesh_n=1 << 28)
PHASES = ("sphere", "catalog", "constrained", "streaming", "mapreduce")
QUOTAS = (5, 4, 3, 3, 2, 2, 2, 2, 2, 1, 1, 1, 1, 1, 1, 1)    # 16 groups, k=32


# --------------------------------------------------------------------------
# reporting
# --------------------------------------------------------------------------

class Report:
    """Check lines plus per-step wall-clock, compile seconds and peak device
    memory.  ``ok`` turns False on the first failed check."""

    PROBED = ("jit_recompiles", "compile_cache_hits", "compile_cache_misses")

    def __init__(self):
        self.ok = True
        self.checks = []                # (phase, name, passed)
        self.compile_s = 0.0
        self.counters = dict.fromkeys(self.PROBED, 0)

    def check(self, phase, name, ok, **nums):
        self.ok &= bool(ok)
        self.checks.append((phase, name, bool(ok)))
        vals = " ".join(f"{k}={v!r}" for k, v in nums.items())
        print(f"[{phase}] check {name}: {'PASS' if ok else 'FAIL'} {vals}",
              flush=True)
        return bool(ok)

    @contextlib.contextmanager
    def step(self, phase, what):
        """Run one step under an enabled ``RunTrace`` (yielded): its compile
        probe counts the backend compiles, their seconds and the
        persistent-cache hits and misses."""
        from repro.obs.trace import RunTrace, activate

        tr, t0 = RunTrace(enabled=True), time.perf_counter()
        with activate(tr):
            yield tr
        dt = time.perf_counter() - t0
        self.compile_s += tr.compile_seconds
        for key in self.PROBED:
            self.counters[key] += tr.counters[key]
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        print(f"[{phase}] {what}: wall_s={dt!r} compile_s="
              f"{tr.compile_seconds!r} compiles="
              f"{tr.counters['jit_recompiles']} "
              f"peak_bytes_in_use={peaks}", flush=True)


# --------------------------------------------------------------------------
# references: plain, and independent of the engine
# --------------------------------------------------------------------------

def ref_pairwise(a, b, metric):
    """float64 distance matrix of two small point sets (numpy)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if metric == "euclidean":
        return np.sqrt(((a[:, None, :] - b[None, :, :]) ** 2).sum(-1))
    if metric == "cosine":
        a = a / np.linalg.norm(a, axis=1, keepdims=True)
        b = b / np.linalg.norm(b, axis=1, keepdims=True)
        return np.arccos(np.clip(a @ b.T, -1.0, 1.0))
    raise ValueError(metric)


@functools.partial(jax.jit, static_argnames=("chunk", "metric", "m"))
def _ref_radius(pts, lab, cen, clab, chunk, metric, m):
    n, d = pts.shape

    def one(c):
        x = jax.lax.dynamic_slice(pts, (c * chunk, 0), (chunk, d))
        lb = jax.lax.dynamic_slice(lab, (c * chunk,), (chunk,))
        own = lb[:, None] == clab[None, :]
        if metric == "euclidean":
            dist = jnp.sqrt(jnp.sum((x[:, None, :] - cen[None, :, :]) ** 2,
                                    axis=-1))
            near = jnp.min(jnp.where(own, dist, jnp.inf), axis=1)
        else:
            xn = x / jnp.linalg.norm(x, axis=1, keepdims=True)
            cn = cen / jnp.linalg.norm(cen, axis=1, keepdims=True)
            sim = jnp.matmul(xn, cn.T, precision=jax.lax.Precision.HIGHEST)
            near = jnp.arccos(jnp.clip(jnp.max(
                jnp.where(own, sim, -jnp.inf), axis=1), -1.0, 1.0))
        return jax.ops.segment_max(near, lb, num_segments=m)

    return jnp.max(jax.lax.map(one, jnp.arange(n // chunk)), axis=0)


def ref_radius(points, centers, metric, labels=None, center_labels=None,
               m=1):
    """Largest distance from a point to its nearest center (per group when
    labels are given: only the point's own group's centers count), in f32
    jnp on the device: direct coordinate differences for euclidean, the
    normalized dot at HIGHEST precision for cosine.  Returns (m,)."""
    n, d = points.shape
    kc = centers.shape[0]
    lab = np.zeros((n,), np.int32) if labels is None else labels
    clab = (np.zeros((kc,), np.int32) if center_labels is None
            else center_labels)
    work = kc * (d if metric == "euclidean" else 1)
    chunk = 1 << max(3, ((1 << 25) // work).bit_length() - 1)
    chunk = min(chunk, n)
    while n % chunk:
        chunk //= 2
    return np.asarray(_ref_radius(
        jnp.asarray(points, jnp.float32), jnp.asarray(lab, jnp.int32),
        jnp.asarray(centers, jnp.float32), jnp.asarray(clab, jnp.int32),
        chunk, metric, m))


def check_result(rep, phase, res, points, metric, measure, *, radius,
                 centers, labels=None, center_labels=None, m=1,
                 indices=True, on_device=None):
    """The shared checks: indices, value against the float64 reference,
    and the certified radius against the radius recomputed over all n
    (from ``on_device``, a device copy of ``points``, when given)."""
    from repro.core.measures import diversity

    sol = np.asarray(res.solution)
    if indices:
        idx = np.asarray(res.indices)
        rep.check(phase, "indices", len(np.unique(idx)) == len(idx) == len(sol)
                  and idx.min() >= 0 and idx.max() < len(points)
                  and np.array_equal(np.asarray(points)[idx], sol),
                  k=len(idx), distinct=int(len(np.unique(idx))))
    want = diversity(measure, ref_pairwise(sol, sol, metric))
    rep.check(phase, "value", np.isclose(res.value, want, rtol=1e-5, atol=0),
              value=float(res.value), reference=float(want),
              rel_err=float(abs(res.value - want) / max(abs(want), 1e-30)))
    got = np.atleast_1d(np.asarray(radius, np.float64))
    ref = ref_radius(points if on_device is None else on_device, centers,
                     metric, labels, center_labels, m)
    rep.check(phase, "radius", bool(np.all(got >= ref * (1 - 1e-4))),
              certified=got.tolist() if got.size > 1 else float(got[0]),
              recomputed=ref.tolist() if ref.size > 1 else float(ref[0]),
              centers=int(np.asarray(centers).shape[0]))


# --------------------------------------------------------------------------
# phases (one chip)
# --------------------------------------------------------------------------

def sphere_points(n, seed, sharding=None, planted=32):
    """The paper's sphere distribution (§7), generated on the device —
    ``data/pipeline.sphere_dataset`` takes ~100 s on a v5e host at 2^26:
    ``planted`` evenly spaced rows on the unit sphere, the rest uniform in
    the 0.8-radius ball.  With a ``sharding`` the points are born sharded,
    so no device ever holds them all."""
    def gen(key):
        kd, kr = jax.random.split(key)
        x = jax.random.normal(kd, (n, 3), jnp.float32)
        x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
        r = 0.8 * jax.random.uniform(kr, (n, 1)) ** (1.0 / 3.0)
        row = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
        return jnp.where(row % (n // planted) == 0, x, x * r)

    return jax.jit(gen, out_shardings=sharding)(jax.random.key(seed))


def phase_sphere(rep, seed, n):
    import repro

    with rep.step("sphere", "generate"):
        on_device = sphere_points(n, seed)
        pts = np.asarray(on_device)           # users hand in host arrays
    with rep.step("sphere", "diversify"):
        res = repro.diversify(
            repro.ProblemSpec(points=pts, k=32, measure="remote-edge"),
            repro.ExecutionSpec(mode="batch", kprime=128))
    with rep.step("sphere", "checks"):
        check_result(rep, "sphere", res, pts, "euclidean", "remote-edge",
                     radius=res.cert.radius, centers=res.coreset.compact(),
                     on_device=on_device)
    return pts, on_device


def phase_catalog(rep, seed, n, d):
    import repro
    from repro.kernels import ops
    from repro.kernels.gmm_update import resolve_interpret

    with rep.step("catalog", "generate"):
        pts = np.random.default_rng(seed + 1).standard_normal(
            (n, d), dtype=np.float32)
        on_device = jax.device_put(pts)
    out, staged = {}, {}
    for pallas in (False, True):
        name = "pallas" if pallas else "jnp"
        with rep.step("catalog", f"diversify[{name}]") as tr:
            out[name] = repro.diversify(
                repro.ProblemSpec(points=pts, k=64, metric="cosine"),
                repro.ExecutionSpec(mode="batch", use_pallas=pallas))
        staged[name] = tr.counters["pallas_compiled"]
        res = out[name]
        with rep.step("catalog", f"checks[{name}]"):
            check_result(rep, f"catalog:{name}", res, pts, "cosine",
                         "remote-edge", radius=res.cert.radius,
                         centers=res.coreset.compact(), indices=not pallas,
                         on_device=on_device)
    a, b = out["jnp"], out["pallas"]
    same = np.array_equal(np.asarray(a.solution), np.asarray(b.solution))
    rep.check("catalog", "pallas_vs_jnp", same and np.isclose(
        a.value, b.value, rtol=1e-5, atol=0),
        value_jnp=float(a.value), value_pallas=float(b.value),
        same_indices=same, kprime_jnp=a.cert.kprime,
        kprime_pallas=b.cert.kprime)
    if not same:        # report where the two selections part
        ia, ib = np.asarray(a.indices), np.asarray(b.indices)
        rep.check("catalog", "pallas_indices", False,
                  differ_at=np.nonzero(ia != ib)[0].tolist()[:8])
    # the timed Pallas run staged compiled (not interpreted) kernels into
    # its programs, the jnp run none, and such a kernel lowers to a Mosaic
    # custom call on this chip
    x = jax.ShapeDtypeStruct((n, d), jnp.float32)
    f = jax.ShapeDtypeStruct((n,), jnp.float32)
    hlo = ops.gmm_topb.lower(x, jax.ShapeDtypeStruct((8, d), jnp.float32), f,
                             jax.ShapeDtypeStruct((n,), jnp.bool_), "cosine",
                             p=128).as_text()
    rep.check("catalog", "pallas_compiled",
              staged["pallas"] > 0 and staged["jnp"] == 0
              and "tpu_custom_call" in hlo and resolve_interpret() is False,
              kernels_staged=staged, tpu_custom_call="tpu_custom_call" in hlo,
              interpret=resolve_interpret())


def zipf_labels(rng, n, m):
    p = 1.0 / np.arange(1, m + 1)
    return rng.choice(m, size=n, p=p / p.sum()).astype(np.int32)


def phase_constrained(rep, seed, n):
    import repro

    m = len(QUOTAS)
    with rep.step("constrained", "generate"):
        on_device = sphere_points(n, seed + 2)
        pts = np.asarray(on_device)
        labels = zipf_labels(np.random.default_rng(seed + 2), n, m)
    with rep.step("constrained", "diversify"):
        res = repro.diversify(repro.ProblemSpec(
            points=pts, k=sum(QUOTAS), labels=labels, quotas=QUOTAS))
    with rep.step("constrained", "checks"):
        counts = np.bincount(labels[np.asarray(res.indices)], minlength=m)
        rep.check("constrained", "quotas",
                  counts.tolist() == list(QUOTAS), counts=counts.tolist())
        cand, cand_lab = res.coreset.flatten()
        check_result(rep, "constrained", res, pts, "euclidean", "remote-edge",
                     radius=res.coreset.radius, centers=pts[cand],
                     labels=labels, center_labels=cand_lab, m=m,
                     on_device=on_device)


def stream_chunks(seed, chunks, chunk):
    for j in range(chunks):
        yield np.random.default_rng([seed, j]).standard_normal(
            (chunk, 3), dtype=np.float32)


def phase_streaming(rep, seed, chunks, chunk):
    import repro

    with rep.step("streaming", "diversify"):
        res = repro.diversify(repro.ProblemSpec(
            points=stream_chunks(seed + 3, chunks, chunk), k=32, dim=3))
    with rep.step("streaming", "checks"):
        from repro.core.measures import diversity

        sol = np.asarray(res.solution)
        want = diversity("remote-edge", ref_pairwise(sol, sol, "euclidean"))
        rep.check("streaming", "value",
                  np.isclose(res.value, want, rtol=1e-5, atol=0),
                  value=float(res.value), reference=float(want),
                  n_seen=res.telemetry["n_seen"])
        cen = res.coreset.compact()
        worst = max(float(ref_radius(c, cen, "euclidean")[0])
                    for c in stream_chunks(seed + 3, chunks, chunk))
        rep.check("streaming", "radius",
                  res.cert.radius >= worst * (1 - 1e-4),
                  certified=float(res.cert.radius), recomputed=worst,
                  centers=int(cen.shape[0]))


def phase_mapreduce(rep, seed, n, sphere=None):
    from jax.sharding import Mesh

    import repro

    if sphere is None:
        with rep.step("mapreduce", "generate"):
            on_device = sphere_points(n, seed)
            sphere = np.asarray(on_device), on_device
    pts, on_device = sphere
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    for name, ex in (("mesh1", dict(mesh=mesh)),
                     ("sim4", dict(num_reducers=4, partition="contiguous"))):
        with rep.step("mapreduce", f"diversify[{name}]"):
            res = repro.diversify(
                repro.ProblemSpec(points=pts, k=32),
                repro.ExecutionSpec(mode="mapreduce", kprime=128, **ex))
        with rep.step("mapreduce", f"checks[{name}]"):
            check_result(rep, f"mapreduce:{name}", res, pts, "euclidean",
                         "remote-edge", radius=res.coreset.radius,
                         centers=res.coreset.compact(), on_device=on_device)


# --------------------------------------------------------------------------
# four chips: shard_map over a 4-device mesh vs 4 simulated reducers
# --------------------------------------------------------------------------

def phase_mesh4(rep, seed, n, devices):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import repro

    mesh = Mesh(np.array(devices[:4]), ("data",))
    with rep.step("mesh4", "generate sharded"):
        pts = jax.block_until_ready(sphere_points(
            n, seed, NamedSharding(mesh, P("data"))))
    rows = [s.data.shape[0] for s in pts.addressable_shards]
    rep.check("mesh4", "input_sharded", len(pts.sharding.device_set) == 4
              and rows == [n // 4] * 4, shard_rows=rows)
    with rep.step("mesh4", "diversify[mesh4]"):
        res = repro.diversify(
            repro.ProblemSpec(points=pts, k=32),
            repro.ExecutionSpec(mode="mapreduce", mesh=mesh, kprime=128))
    host = np.asarray(pts)
    summary = {}

    def checked(name, res):
        with rep.step("mesh4", f"checks[{name}]"):
            check_result(rep, f"mesh4:{name}", res, host, "euclidean",
                         "remote-edge", radius=res.coreset.radius,
                         centers=res.coreset.compact())
        summary[name] = (res.coreset.compact(), np.asarray(res.solution),
                         res.value)

    checked("mesh4", res)
    del res, pts            # the sharded input, before one chip takes all n
    with rep.step("mesh4", "diversify[sim4]"):
        res = repro.diversify(
            repro.ProblemSpec(points=host, k=32),
            repro.ExecutionSpec(mode="mapreduce", num_reducers=4,
                                partition="contiguous", kprime=128))
    checked("sim4", res)
    (ca, sa, va), (cb, sb, vb) = summary["mesh4"], summary["sim4"]
    same = ca.shape == cb.shape and np.array_equal(ca, cb)
    # where each reducer's selection first parts (-1: identical)
    parts = ([int(np.argmin(np.all(x == y, axis=1))) if not
              np.array_equal(x, y) else -1
              for x, y in zip(np.split(ca, 4), np.split(cb, 4))]
             if ca.shape == cb.shape else None)
    rep.check("mesh4", "mesh_vs_sim", same and np.array_equal(sa, sb)
              and va == vb, coreset_rows=[len(ca), len(cb)],
              same_coreset=bool(same), reducer_first_difference=parts,
              same_solution=bool(np.array_equal(sa, sb)),
              value_mesh=float(va), value_sim=float(vb))


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def run(rep, seed, sizes, phases=PHASES, devices=None):
    """Run the one-chip ``phases`` (or, with ``devices``, only the 4-chip
    MapReduce comparison) at ``sizes``; every check reports to ``rep``.  A
    phase that raises fails the run, and the next phase starts."""
    if devices is not None:
        todo = {"mesh4": lambda: phase_mesh4(rep, seed, sizes["mesh_n"],
                                             devices)}
    else:
        sphere = {}         # the sphere batch input, reused by mapreduce
        todo = {
            "sphere": lambda: sphere.update(
                pts=phase_sphere(rep, seed, sizes["sphere_n"])),
            "catalog": lambda: phase_catalog(rep, seed, sizes["catalog_n"],
                                             sizes["catalog_d"]),
            "constrained": lambda: phase_constrained(rep, seed,
                                                     sizes["grouped_n"]),
            "streaming": lambda: phase_streaming(
                rep, seed, sizes["stream_chunks"], sizes["stream_chunk"]),
            "mapreduce": lambda: phase_mapreduce(
                rep, seed, sizes["sphere_n"], sphere.get("pts")),
        }
        todo = {ph: todo[ph] for ph in PHASES if ph in phases}
    for name, fn in todo.items():
        t0 = time.perf_counter()
        try:
            fn()
        except Exception as e:                  # noqa: BLE001 - report, fail
            rep.check(name, "ran", False, error=f"{type(e).__name__}: {e}")
        print(f"[{name}] phase_s={time.perf_counter() - t0!r}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache

    print(f"device: {devices[0].device_kind} x{len(devices)}, "
          f"jax {jax.__version__}, compile cache "
          f"{enable_compile_cache(ROOT)}", flush=True)
    rep = Report()
    t0 = time.perf_counter()
    run(rep, args.seed, FULL, devices=devices if args.chips == 4 else None)
    print(f"total: wall_s={time.perf_counter() - t0!r} "
          f"compile_s={rep.compile_s!r} " + " ".join(
              f"{k}={v}" for k, v in rep.counters.items()), flush=True)
    if not rep.ok:
        print("chip_smoke: a check failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
