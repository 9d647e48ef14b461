"""Sort cells: back-to-back key-value sorts of seeded f32 keys with their
int32 original index as payload.

Each sort in the window takes one of ``inputs`` unsorted arrays resident
in HBM, never a previous output (the bitonic network does the same work
on any input; a merge or radix sort would not). ``IN_FLIGHT`` sorts are
queued while the host waits for the oldest, so a host that stalls for
less than that many sorts' time leaves the device busy. After the window
the last output of each input is checked against XLA's sort
(``bench/reference.py``): keys in order, every (key, payload) pair
intact, the payload a permutation, and on several chips every key on
exactly one rank, in global order, with no overflow.
"""
from __future__ import annotations

import collections
import functools
import time

import numpy as np

from bench import common, reference, traffic as T

#: Sorts queued behind the one the host waits for.
IN_FLIGHT = 2


def _program(cfg: dict, tr: dict, devs):
    """The timed function (keys, payload) -> output, and its mesh."""
    import jax
    from repro import core as ak

    if not tr["distributed"]:
        def sort_kv(k, v):
            return ak.merge_sort_by_key(k, v)
        return jax.jit(sort_kv), None
    from jax.sharding import Mesh

    mesh = Mesh(np.array(devs), ("data",))

    def sihsort(k, v):
        return ak.sihsort_sharded(
            k, mesh, "data", payload=v,
            capacity_factor=cfg["capacity_factor"],
            exchange=cfg["exchange"])
    return jax.jit(sihsort), mesh


def inputs(cfg: dict, tr: dict, seed: int, devs, mesh):
    """``tr["inputs"]`` key arrays and the shared payload, on the device
    (sharded over the mesh on several chips)."""
    import jax
    import jax.numpy as jnp

    n = cfg["n_per_chip"] * len(devs)
    if mesh is None:
        from jax.sharding import SingleDeviceSharding

        shard = SingleDeviceSharding(devs[0])
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        shard = NamedSharding(mesh, P("data"))
    keys = [T.sort_keys(jax.random.PRNGKey(s), n, cfg["key_distribution"],
                        cfg["key_dtype"], shard)
            for s in common.seeds(seed, tr["inputs"])]
    pay = jax.jit(lambda: jnp.arange(n, dtype=jnp.dtype(cfg["payload_dtype"])),
                  out_shardings=shard)()
    return keys, pay


def sort_window(run, *, program_hook=None):
    import jax

    cfg, tr, devs = run.config, run.traffic, run.devices
    fn, mesh = _program(cfg, tr, devs)
    if program_hook is not None:
        fn = program_hook(fn, mesh)
    keys, pay = inputs(cfg, tr, run.seed, devs, mesh)
    # warm-up: compile, then one sort of every input
    jax.block_until_ready([fn(k, pay) for k in keys])
    setup_s = time.perf_counter() - common.T_START

    outs = [None] * len(keys)
    count, queued, done_t = 0, collections.deque(), []
    run.compiles.on = True
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < run.seconds:
        i = count % len(keys)
        outs[i] = fn(keys[i], pay)
        queued.append(outs[i])
        count += 1
        if run.tracer is not None:
            run.tracer.on_step(count)
        if len(queued) > IN_FLIGHT:
            jax.block_until_ready(queued.popleft())
            done_t.append(time.perf_counter())
    while queued:
        jax.block_until_ready(queued.popleft())
        done_t.append(time.perf_counter())
    window_s = time.perf_counter() - t0
    run.compiles.on = False
    gaps = np.diff(done_t) if len(done_t) > 1 else np.zeros(1)
    common.log(f"sorts completed every {np.median(gaps) * 1e3:.1f} ms "
               f"(median), longest wait {gaps.max() * 1e3:.1f} ms")
    if run.tracer is not None and run.tracer.running:
        run.tracer.stop()
    device = common.device_report(devs)
    n_total = cfg["n_per_chip"] * len(devs)
    common.log(f"window: {window_s:.3f} s, {count} sorts of {n_total} "
               f"pairs, {run.compiles.count} compiles inside")
    return {"keys": keys, "outs": outs, "count": count, "setup_s": setup_s,
            "window_s": window_s, "device": device, "n_total": n_total,
            "mesh": mesh}


@functools.lru_cache(maxsize=None)
def _part_checks():
    import jax
    import jax.numpy as jnp

    def part(ref_k, keys, vals, pay, off, cnt):
        """One rank's output (padded, ``cnt`` valid) against the sorted
        reference from ``off`` on: wrong keys, broken pairs, and a tally
        of every payload seen."""
        width = vals.shape[0]
        idx = jnp.arange(width)
        valid = idx < cnt
        # a count past the end is caught by the caller's tally of counts
        want = ref_k[jnp.clip(off + idx, 0, ref_k.shape[0] - 1)]
        keys_wrong = jnp.sum(valid & (vals != want))
        p = jnp.where(valid, pay, 0)
        pairs_wrong = jnp.sum(valid & (keys[p] != vals))
        return keys_wrong, pairs_wrong

    def tally(seen, pay, cnt):
        valid = jnp.arange(pay.shape[0]) < cnt
        return seen.at[jnp.where(valid, pay, seen.shape[0])].add(
            1, mode="drop")

    return jax.jit(part), jax.jit(tally, donate_argnums=0)


def check_output(keys, parts, overflow: int, *, device) -> dict:
    """Counts of what is wrong in one sort's output. ``parts``: per rank,
    in rank order, (keys, payload, valid count) on any device."""
    import jax
    import jax.numpy as jnp

    part, tally = _part_checks()
    with jax.default_device(device):
        k = jax.device_put(keys, device)
        ref_k = reference.sorted_keys(k)
        seen = jnp.zeros(k.shape, jnp.int32)
        keys_wrong = pairs_wrong = 0
        off = 0
        for vals, pay, cnt in parts:
            vals = jax.device_put(vals, device)
            pay = jax.device_put(pay, device)
            kw, pw = part(ref_k, k, vals, pay, jnp.int32(off),
                          jnp.int32(cnt))
            keys_wrong += int(kw)
            pairs_wrong += int(pw)
            seen = tally(seen, pay, jnp.int32(cnt))
            off += int(cnt)
        missing = int(jnp.sum(seen != 1))
    return {"keys_wrong": keys_wrong + abs(off - int(k.shape[0])),
            "pairs_wrong": pairs_wrong + missing, "overflow": overflow}


def output_parts(out, ranks: int):
    """(keys, payload, count) per rank of one sort's output."""
    if ranks == 1:
        k, v = out
        return [(k, v, k.shape[0])], 0
    counts = np.asarray(out.count).reshape(-1)
    vals = [s.data.reshape(-1) for s in sorted(
        out.values.addressable_shards, key=lambda s: s.index[0].start)]
    pays = [s.data.reshape(-1) for s in sorted(
        out.payload.addressable_shards, key=lambda s: s.index[0].start)]
    return ([(v, p, int(c)) for v, p, c in zip(vals, pays, counts)],
            int(np.asarray(out.overflow).sum()))


def readings(w: dict, devs) -> dict:
    """Worst counts over the last output of every input."""
    worst = {"keys_wrong": 0, "pairs_wrong": 0, "overflow": 0}
    for i, k in enumerate(w["keys"]):
        # each output is freed once checked: the check needs the room
        out, w["outs"][i] = w["outs"][i], None
        if out is None:
            continue
        parts, overflow = output_parts(out, len(devs))
        del out
        got = check_output(k, parts, overflow, device=devs[0])
        worst = {n: max(worst[n], got[n]) for n in worst}
    return worst


def run(run, *, program_hook=None) -> None:
    w = sort_window(run, program_hook=program_hook)
    t0 = time.perf_counter()
    got = readings(w, run.devices)
    common.log(f"checked the last output of each of {len(w['keys'])} inputs "
               f"against XLA's sort in {time.perf_counter() - t0:.1f} s")
    checks = {name: {"value": got[name], "limit": run.limits[name]}
              for name in ("keys_wrong", "pairs_wrong", "overflow")}
    bytes_in = w["n_total"] * (np.dtype(run.config["key_dtype"]).itemsize
                               + np.dtype(run.config["payload_dtype"])
                               .itemsize)
    e2e = {"sort_gbps": w["count"] * bytes_in / w["window_s"] / 1e9,
           "setup_s": w["setup_s"]}
    layer = {"sorts": w["count"], "window_s": w["window_s"],
             "n_per_chip": run.config["n_per_chip"],
             "chips": len(run.devices)}
    run.finish(e2e, layer, checks, attempted=w["count"], failed=0,
               device=w["device"])
