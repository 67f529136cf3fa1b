"""Hand-sweep extra bench configs beyond bench.py's CONFIGS list.

Round-5 on-chip tuning: the driver sweep found bhsd+hd128+noremat+accum4
+chunk at 0.4548 MFU; this script probes the neighborhood (batch size,
accum depth, loss-chunk size, flash block sizes) one killable child per
config, appending every result to BENCH_EXTRA_r05.json as it lands.

Usage:
  python scripts/bench_extra.py            # parent: run the sweep
  python scripts/bench_extra.py --one IDX  # child: measure one config
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "BENCH_EXTRA_r05.json")

BASE = {"attention_layout": "bhsd", "num_attention_heads": 8,
        "num_key_value_heads": 8, "use_recompute": False,
        "loss_chunk": 512, "_accum": 4}

EXTRA = [
    # batch scaling: 2x tokens/step at the same microbatch size (accum 8)
    ("winner+B16+accum8", dict(BASE, _B=16, _accum=8)),
    # bigger microbatch (4 instead of 2): better MXU fill if memory allows
    ("winner+B16+accum4", dict(BASE, _B=16, _accum=4)),
    ("winner+accum2", dict(BASE, _accum=2)),
    # loss-chunk size: vocab-proj chunking trades live memory for launches
    ("winner+chunk1024", dict(BASE, loss_chunk=1024)),
    ("winner+chunk256", dict(BASE, loss_chunk=256)),
    # no chunking at all (loss_chunk=0 -> whole-row vocab projection)
    ("winner+nochunk", dict(BASE, loss_chunk=0)),
    # flash block sweep around the default
    ("winner+fbq512k256", dict(BASE, flash_block_q=512, flash_block_k=256)),
    ("winner+fbq256k512", dict(BASE, flash_block_q=256, flash_block_k=512)),
]


def main_one(idx):
    import bench
    name, overrides = EXTRA[idx]
    print(json.dumps(bench._measure_config(name, dict(overrides))))
    return 0


def main():
    import bench
    results = []
    if os.path.exists(OUT):
        try:
            results = json.load(open(OUT))["configs"]
        except Exception:
            pass
    # only successful measurements block a re-run: a failed config
    # (mfu=0 err entry) is retried on the next invocation
    done = {r["name"] for r in results if r.get("mfu")}
    results = [r for r in results if r.get("mfu")]
    for i, (name, _) in enumerate(EXTRA):
        if name in done:
            continue
        t0 = time.time()
        rc, out, err = bench._run(
            [os.path.abspath(__file__), "--one", str(i)], 420)
        r = bench._parse_result(rc, out)  # tolerant of truncated stdout
        if r is not None and r.get("mfu"):
            results.append(r)
            print(f"{name}: mfu={r['mfu']:.4f} step={r['step_ms']:.1f}ms "
                  f"({time.time()-t0:.0f}s)")
        else:
            results.append({"name": name, "mfu": 0.0,
                            "err": (f"rc={rc}" + (" hang" if rc == 124 else "")
                                    + f"; stderr tail: {err.strip()[-200:]}")})
            print(f"{name}: FAILED rc={rc}")
        tmp = OUT + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"configs": results}, f, indent=1)
        os.replace(tmp, OUT)
    best = max((r for r in results if r.get("mfu")), key=lambda r: r["mfu"],
               default=None)
    if best:
        print(f"BEST extra: {best['name']} mfu={best['mfu']:.4f}")
    return 0


if __name__ == "__main__":
    if "--one" in sys.argv:
        sys.exit(main_one(int(sys.argv[sys.argv.index("--one") + 1])))
    sys.exit(main())
