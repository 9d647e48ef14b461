"""EXPERIMENTS.md table generator: dry-run + roofline results -> markdown.

Reads results/dryrun/*.json and results/roofline/*.json and emits the
§Dry-run and §Roofline tables. Adds a fusion-adjusted memory estimate:
XLA:CPU's ``bytes accessed`` counts every HLO op's operands with almost no
fusion, over-stating real (TPU, fused) HBM traffic by an order of
magnitude; the analytic estimate below counts the traffic a fused TPU
execution actually pays — parameter reads, optimizer state, activation
save/restore under remat, KV/SSM cache sweeps — and is used for the
roofline-fraction score next to the raw-HLO prescription.

    PYTHONPATH=src:. python -m benchmarks.report [--dryrun-dir ...]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def _cfg(arch):
    from repro.configs import base as CB

    return CB.load_config(arch)


def _shape(name):
    from repro.configs.base import SHAPES

    return SHAPES[name]


def count_params(cfg):
    import jax

    from repro.models import model as M

    shapes = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg)
    )
    return sum(x.size for x in jax.tree.leaves(shapes))


def active_params(cfg):
    n = count_params(cfg)
    if cfg.family != "moe":
        return n
    routed_layers = cfg.n_layers - int(cfg.first_layer_dense)
    routed = routed_layers * cfg.n_experts * 3 * cfg.d_model * cfg.d_ff
    return n - routed + routed * cfg.top_k / cfg.n_experts


def cache_bytes_per_chip(cfg, B, S, n_dev, tp=16):
    """Decode-cache bytes on one chip (mirrors models.sharding placement)."""
    import jax

    from repro.models import model as M

    specs = M.cache_specs(cfg, batch=B, cache_len=S)
    total = sum(
        s.size * s.dtype.itemsize for s in jax.tree.leaves(specs)
    )
    return total / n_dev  # caches shard across the full mesh


def analytic_bytes_per_chip(cfg, shape_name, n_dev, kind, tp=16):
    """Fused-execution HBM-traffic estimate per chip per step."""
    s = _shape(shape_name)
    B, S = s["batch"], s["seq"]
    dp = n_dev // tp
    N = count_params(cfg)
    Na = active_params(cfg)
    d = cfg.d_model

    if kind == "train":
        tokens_dev = B * S / dp
        # each chip reads its TP shard of every (gathered) weight fwd,
        # again in bwd, and once more for the remat forward
        param_io = (N / tp) * 2 * 3
        # optimizer: grads f32 + m/v read+write + param update (sharded
        # over ALL devices — ZeRO)
        opt_io = (N / n_dev) * (4 + 16 + 4)
        # activations: ~8 d-wide tensors per layer saved fwd + read bwd
        act_io = cfg.n_layers * tokens_dev * d * 2 * 8 * 2
        return param_io + opt_io + act_io
    if kind == "prefill":
        tokens_dev = B * S / dp
        param_io = (Na / tp) * 2
        act_io = cfg.n_layers * tokens_dev * d * 2 * 8
        return param_io + act_io
    # decode: weights + one full cache sweep per token
    param_io = (Na / tp) * 2
    return param_io + cache_bytes_per_chip(cfg, B, S, n_dev, tp)


def load(dirname):
    out = {}
    for f in glob.glob(os.path.join(dirname, "*.json")):
        if os.path.basename(f) == "summary.json":
            continue  # our own aggregate output
        rec = json.load(open(f))
        out[os.path.basename(f)[:-5]] = rec
    return out


def dryrun_table(recs, mesh_name):
    lines = [
        "| arch | shape | compile s | HLO GFLOPs/chip | arg GB/chip | "
        "coll MB/chip (counted-once) |",
        "|---|---|---|---|---|---|",
    ]
    for tag in sorted(recs):
        r = recs[tag]
        if not tag.endswith("." + mesh_name):
            continue
        coll = sum(r["collectives"]["bytes"].values())
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compile_s']} | "
            f"{r['flops']/1e9:,.1f} | "
            f"{r['memory']['argument_bytes']/r['devices']/1e9:.2f} | "
            f"{coll/1e6:,.1f} |"
        )
    return "\n".join(lines)


def _fallback_roofline(dr_recs):
    """Baseline rows for cells the unroll-extrapolation hasn't reached:
    derive terms from the v3 dry-run record by scaling the counted-once
    program cost by the scanned-unit count (upper-bounds the true value —
    the non-loop base gets multiplied too; tier-labeled in the table)."""
    import dataclasses

    from benchmarks.roofline import (depth_variants, model_flops_per_chip,
                                     active_params)

    out = {}
    for tag, r in dr_recs.items():
        if not tag.endswith(".single"):
            continue
        arch, shape = r["arch"], r["shape"]
        cfg = _cfg(arch)
        _, _, units, _ = depth_variants(cfg)
        scale = units if r["kind"] != "decode" else units
        rec = {
            "arch": arch, "shape": shape, "devices": r["devices"],
            "flops": r["flops"] * scale,
            "bytes": r["bytes_accessed"] * scale,
            "coll_bytes": sum(r["collectives"]["bytes"].values()) * scale,
            "t_compute_s": r["flops"] * scale / PEAK_FLOPS,
            "t_memory_s": r["bytes_accessed"] * scale / HBM_BW,
            "t_collective_s":
                sum(r["collectives"]["bytes"].values()) * scale / ICI_BW,
            "model_flops_per_chip": model_flops_per_chip(
                cfg, shape, r["devices"]),
            "tier": "dryrun-scaled",
        }
        rec["useful_flops_ratio"] = (
            rec["model_flops_per_chip"] / max(rec["flops"], 1.0)
        )
        out[f"{arch}.{shape}.single"] = rec
    return out


def roofline_table(recs, dr_recs=None):
    if dr_recs:
        fallback = _fallback_roofline(dr_recs)
        merged = dict(fallback)
        merged.update(recs)  # full-quality rows win
        recs = merged
    lines = [
        "| arch | shape | t_compute | t_mem(HLO) | t_mem(est) | t_coll | "
        "bottleneck | MODEL/HLO flops | roofline frac | tier |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    rows = []
    for tag in sorted(recs):
        r = recs[tag]
        cfg = _cfg(r["arch"])
        kind = _shape(r["shape"])["kind"]
        est = analytic_bytes_per_chip(
            cfg, r["shape"], r["devices"], kind
        )
        t_est = est / HBM_BW
        t_c, t_m, t_x = (r["t_compute_s"], r["t_memory_s"],
                         r["t_collective_s"])
        dom = max((("compute", t_c), ("memory", t_est),
                   ("collective", t_x)), key=lambda kv: kv[1])[0]
        frac = r["model_flops_per_chip"] / PEAK_FLOPS / max(
            t_c, t_est, t_x
        )
        r2 = dict(r)
        r2.update(t_mem_est_s=t_est, bottleneck_est=dom,
                  roofline_fraction_est=min(frac, 1.0))
        rows.append(r2)
        lines.append(
            f"| {r['arch']} | {r['shape']} | {t_c:.3e} | {t_m:.3e} | "
            f"{t_est:.3e} | {t_x:.3e} | {dom} | "
            f"{r['useful_flops_ratio']:.2f} | {min(frac,1.0):.1%} | "
            f"{r.get('tier', 'unroll-extrapolated')} |"
        )
    return "\n".join(lines), rows


def serving_table(json_path=None):
    """Serving trajectory (BENCH_serve.json): tok/s, fused-vs-unfused
    sampler launches per decode step, slot utilisation, and — for entries
    recorded since the paged KV cache landed — the memory-economics
    columns (resident bytes per active token paged vs contiguous,
    page-pool occupancy, prefix-reuse hit rate) and the chaos-gate column
    (injected faults / preemptions / retries / rejections / timeouts of
    the scripted fault run). Entries predating the paged engine or the
    fault-tolerance tier show '-'. Missing/invalid files degrade to a
    hint line, never an error."""
    path = json_path or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_serve.json",
    )
    if not os.path.exists(path):
        return (f"(no serving trajectory at {path}; populate with "
                f"`PYTHONPATH=src python -m benchmarks.serving`)")
    lines = [
        "| arch | req/slots | tokens (EOS-aware / naive) | steps | "
        "launches/step fused vs unfused | slot util | tok/s (wallclock) | "
        "resident B/token paged vs contig | occupancy | prefix hit rate | "
        "chaos (faults/preempt/retry/reject/timeout) |",
        "|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    try:
        with open(path) as f:
            entries = json.load(f)["entries"]
        for e in entries:
            sl = e.get("sampler_launches", {})
            wc = e.get("wallclock", {})
            pg = e.get("paged") or {}
            bpt = pg.get("resident_bytes_per_active_token") or {}
            mem = (
                f"{bpt.get('paged')} vs {bpt.get('contiguous')} "
                f"({bpt.get('ratio')}x)" if bpt else "-"
            )
            occ = pg.get("mean_occupancy", "-")
            hit = (pg.get("prefix_reuse") or {}).get("hit_rate", "-")
            ch = e.get("chaos") or {}
            chaos = (
                f"{ch.get('faults_injected')}/{ch.get('preemptions')}/"
                f"{ch.get('step_retries')}/{ch.get('rejections')}/"
                f"{ch.get('timeouts')}" if ch else "-"
            )
            lines.append(
                f"| {e.get('arch')} | {e.get('requests')}/{e.get('slots')} "
                f"| {e.get('tokens_eos_aware')} / {e.get('tokens_naive')} | "
                f"{e.get('decode_steps')} | "
                f"{sl.get('fused')} vs {sl.get('unfused')} | "
                f"{e.get('mean_slot_util')} | {wc.get('tok_s', '-')} | "
                f"{mem} | {occ} | {hit} | {chaos} |"
            )
    except (OSError, json.JSONDecodeError, KeyError, TypeError,
            AttributeError) as e:
        # hand-edited/corrupt trajectory: degrade, never crash the report
        return f"(serving trajectory at {path} unreadable: {e})"
    return "\n".join(lines)


def obs_table(json_path=None):
    """Observability trajectory (the ``obs`` sub-entry of
    BENCH_serve.json, DESIGN.md §11): whether the telemetry-on run stayed
    bitwise identical to telemetry-off, the per-primitive launch tally it
    attributed, and the span/instant inventory of the exported Perfetto
    trace. Entries predating the telemetry tier show '-'. Missing/invalid
    files degrade to a hint line, never an error."""
    path = json_path or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_serve.json",
    )
    if not os.path.exists(path):
        return (f"(no serving trajectory at {path}; populate with "
                f"`PYTHONPATH=src python -m benchmarks.serving`)")
    lines = [
        "| arch | tokens identical | launches (attributed) | trace spans "
        "(ak.*) | instants | preempt/retries/faults |",
        "|---|---|---|---|---|---|",
    ]
    try:
        with open(path) as f:
            entries = json.load(f)["entries"]
        for e in entries:
            ob = e.get("obs") or {}
            if not ob:
                lines.append(f"| {e.get('arch')} | - | - | - | - | - |")
                continue
            la = ob.get("launches") or {}
            launches = ", ".join(
                f"{k}={v}" for k, v in sorted(la.items())) or "0"
            lines.append(
                f"| {e.get('arch')} | "
                f"{'yes' if ob.get('tokens_identical') else 'NO'} | "
                f"{launches} | {ob.get('trace_spans')} "
                f"({ob.get('primitive_spans')}) | "
                f"{len(ob.get('instants') or [])} | "
                f"{ob.get('preemptions')}/{ob.get('step_retries')}/"
                f"{ob.get('faults_injected')} |"
            )
    except (OSError, json.JSONDecodeError, KeyError, TypeError,
            AttributeError) as e:
        return f"(serving trajectory at {path} unreadable: {e})"
    return "\n".join(lines)


def moe_dispatch_table(json_path=None):
    """MoE dispatch trajectory (BENCH_moe.json): modelled HBM bytes of the
    capacity-padded vs bucketed layouts at the gate config, the byte
    ratio against its gate floor, counted trace-time launches, and the
    segmented-primitive oracle/sweep tallies. Missing/invalid files
    degrade to a hint line, never an error."""
    path = json_path or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_moe.json",
    )
    if not os.path.exists(path):
        return (f"(no MoE dispatch trajectory at {path}; populate with "
                f"`PYTHONPATH=src:. python -m benchmarks.moe_dispatch`)")
    lines = [
        "| config (T/k/E/d/ff/cf) | padded MB | bucketed MB | ratio "
        "(gate) | launches b/p | oracle checks | sweep entries |",
        "|---|---|---|---|---|---|---|",
    ]
    try:
        with open(path) as f:
            entries = json.load(f)["entries"]
        for e in entries:
            c = e.get("config") or {}
            cfg = (f"{c.get('T')}/{c.get('k')}/{c.get('E')}/{c.get('d')}/"
                   f"{c.get('ff')}/{c.get('cf')}")
            pb = (e.get("padded") or {}).get("total_bytes")
            bb = (e.get("bucketed") or {}).get("total_bytes")
            la = e.get("launches") or {}
            lines.append(
                f"| {cfg} | {pb / 1e6:.1f} | {bb / 1e6:.1f} | "
                f"{e.get('bytes_ratio')}x (>={e.get('gate_min_ratio')}x) | "
                f"{la.get('bucketed')}/{la.get('padded')} | "
                f"{e.get('oracle_checks')} | {e.get('sweep_entries')} |"
            )
    except (OSError, json.JSONDecodeError, KeyError, TypeError,
            AttributeError) as e:
        return f"(MoE dispatch trajectory at {path} unreadable: {e})"
    return "\n".join(lines)


def hetero_table(json_path=None):
    """Heterogeneous co-sort trajectory (the ``sort_hetero`` entries of
    BENCH_sort.json, DESIGN.md §12): per-rank backend, partition weight and
    received rows side by side with the modelled uniform-vs-proportional
    makespan — the visible record that the splitters actually cut
    throughput-proportionally and that it paid. Missing/invalid files
    degrade to a hint line, never an error."""
    path = json_path or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_sort.json",
    )
    if not os.path.exists(path):
        return (f"(no sort trajectory at {path}; populate with "
                f"`PYTHONPATH=src:. python -m benchmarks.sort_throughput`)")
    lines = [
        "| n (P) | rank: backend weight -> rows | overflow | makespan "
        "uniform vs proportional | gain | weight source |",
        "|---|---|---|---|---|---|",
    ]
    try:
        with open(path) as f:
            entries = [e for e in json.load(f)["entries"]
                       if e.get("entry") == "sort_hetero"]
        if not entries:
            return ("(no sort_hetero entries yet; populate with "
                    "`PYTHONPATH=src:. python -m benchmarks.run --quick`)")
        for e in entries:
            ranks = " ".join(
                f"r{i}:{b[:3]} {w:.3f}->{c}"
                for i, (b, w, c) in enumerate(zip(
                    e.get("backends") or [],
                    e.get("weights") or [],
                    e.get("received_rows") or [],
                ))
            )
            uni = e.get("modelled_makespan_s_uniform")
            prop = e.get("modelled_makespan_s_proportional")
            span = (
                f"{uni * 1e6:.1f}us vs {prop * 1e6:.1f}us"
                if uni is not None and prop is not None else "-"
            )
            src = sorted(set(e.get("weight_sources") or [])) or ["-"]
            lines.append(
                f"| {e.get('n')} ({e.get('nranks')}) | {ranks} | "
                f"{e.get('overflow')} | {span} | "
                f"{e.get('makespan_gain'):.2f}x | {'/'.join(src)} |"
            )
    except (OSError, json.JSONDecodeError, KeyError, TypeError,
            AttributeError) as e:
        return f"(sort trajectory at {path} unreadable: {e})"
    return "\n".join(lines)


def tuned_vs_default_table(cache_path=None):
    """Per-primitive modelled speedup of the autotuned knobs over the
    default resolution, read from the repro.tune cache — makes the perf
    trajectory of *tuning itself* visible across PRs (the BENCH_autotune
    analogue of the roofline tables). Missing/foreign caches degrade to a
    hint line, never an error."""
    try:
        from repro.tune import cache as tcache
    except ImportError:
        return "(repro.tune not importable; run with PYTHONPATH=src:.)"
    path = cache_path or tcache.default_path()
    if not os.path.exists(path):
        return (f"(no autotune cache at {path}; populate with "
                f"`PYTHONPATH=src python -m repro.tune --model`)")
    try:
        doc = tcache.validate_file(path)
    except (ValueError, json.JSONDecodeError) as e:
        return f"(autotune cache at {path} failed validation: {e})"
    fp = doc["fingerprint"]
    lines = [
        f"cache: {path} — device {fp['device_kind']} "
        f"backend={fp['backend']} interpret={fp['interpret']}",
        "",
        "| key | chosen backend | knobs (non-default) | modelled speedup "
        "| source |",
        "|---|---|---|---|---|",
    ]
    for key in sorted(doc["entries"]):
        e = doc["entries"][key]
        knobs = ", ".join(
            f"{k}={v}" for k, v in sorted((e.get("knobs") or {}).items())
        )
        sp = e.get("speedup")
        lines.append(
            f"| {key} | {e.get('backend')} | {knobs or '(defaults)'} | "
            f"{f'{sp:.2f}x' if sp else '-'} | {e.get('source')} |"
        )
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun-dir", default="results/dryrun")
    ap.add_argument("--roofline-dir", default="results/roofline")
    ap.add_argument("--autotune-cache", default=None,
                    help="repro.tune cache JSON (default: the tune "
                         "subsystem's default path)")
    ap.add_argument("--serve-json", default=None,
                    help="serving trajectory JSON (default: the repo's "
                         "BENCH_serve.json)")
    ap.add_argument("--moe-json", default=None,
                    help="MoE dispatch trajectory JSON (default: the "
                         "repo's BENCH_moe.json)")
    ap.add_argument("--sort-json", default=None,
                    help="sort trajectory JSON with the sort_hetero "
                         "co-sort entries (default: the repo's "
                         "BENCH_sort.json)")
    ap.add_argument("--out", default="results/report.md")
    args = ap.parse_args()

    dr = load(args.dryrun_dir)
    rl = load(args.roofline_dir)
    parts = ["## Dry-run (single pod, 16x16 = 256 chips)\n",
             dryrun_table(dr, "single"),
             "\n\n## Dry-run (multi-pod, 2x16x16 = 512 chips)\n",
             dryrun_table(dr, "multi")]
    if rl or dr:
        tbl, rows = roofline_table(rl, dr)
        parts += ["\n\n## Roofline (single pod)\n", tbl]
        with open(os.path.join(args.roofline_dir, "summary.json"),
                  "w") as f:
            json.dump(rows, f, indent=1, default=float)
    parts += ["\n\n## Serving (continuous-batching engine)\n",
              serving_table(args.serve_json)]
    parts += ["\n\n## Observability (telemetry overhead gate)\n",
              obs_table(args.serve_json)]
    parts += ["\n\n## MoE dispatch (bucketed vs capacity-padded)\n",
              moe_dispatch_table(args.moe_json)]
    parts += ["\n\n## Heterogeneous co-sort (mixed-backend mesh)\n",
              hetero_table(args.sort_json)]
    parts += ["\n\n## Tuned vs default (autotune cache)\n",
              tuned_vs_default_table(args.autotune_cache)]
    text = "".join(parts)
    with open(args.out, "w") as f:
        f.write(text)
    print(text)


if __name__ == "__main__":
    main()
