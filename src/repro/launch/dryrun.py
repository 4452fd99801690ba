"""Multi-pod dry-run: AOT-lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: 512 host
placeholder devices stand in for 2 pods x 256 chips; ``.lower().compile()``
must succeed for every cell, and the compiled artifact yields the roofline
terms (launch/hlo_analysis.py) recorded in EXPERIMENTS.md.

Usage:
    python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k --mesh multi
    python -m repro.launch.dryrun --all --mesh both --out results/dryrun.json

``--all`` forks one subprocess per cell (fresh XLA state; a crashing cell
cannot take down the sweep) and merges results incrementally into --out.
"""

# Before ANY other import — jax locks the platform and the device count on
# first init.  The dry-run compiles for 512 placeholder CPU devices, so it
# stays off any accelerator: neither this process nor the per-cell
# children it starts (they inherit the environment) may hold a chip.
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse      # noqa: E402
import json          # noqa: E402
import subprocess    # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402


def run_cell(arch: str, shape: str, multi_pod: bool,
             variant: str = "baseline") -> dict:
    import jax

    from repro.configs import get_arch
    from repro.launch.hlo_analysis import parse_collectives, roofline
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_cell
    from repro.launch.variants import apply_variant

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_devices = mesh.devices.size
    rec = {
        "arch": arch, "shape": shape, "variant": variant,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(n_devices),
        "family": get_arch(arch).family,
    }
    t0 = time.time()
    cell = build_cell(arch, shape, mesh, unroll=not multi_pod,
                      **apply_variant(arch, shape, variant))
    if cell.skip_reason:
        rec.update(status="SKIP", skip_reason=cell.skip_reason)
        return rec
    rec["kind"] = cell.kind
    with mesh:
        jf = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                     out_shardings=cell.out_shardings,
                     donate_argnums=cell.donate)
        lowered = jf.lower(*cell.args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text())
    rl = roofline(cost, coll, n_devices, cell.model_flops)
    rec.update(
        status="OK",
        lower_s=round(t1 - t0, 2), compile_s=round(t2 - t1, 2),
        memory={
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "peak_est_bytes": int(mem.argument_size_in_bytes
                                  + mem.output_size_in_bytes
                                  + mem.temp_size_in_bytes
                                  - mem.alias_size_in_bytes),
        },
        cost={"flops": float(cost.get("flops", 0.0)),
              "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
              "transcendentals": float(cost.get("transcendentals", 0.0))},
        collectives={"ops": coll.ops,
                     "logical_bytes": coll.logical_bytes,
                     "wire_bytes": float(coll.wire_bytes)},
        roofline=rl.as_dict(),
    )
    return rec


def _merge_out(out_path: str, rec: dict) -> None:
    data = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            data = json.load(f)
    key = f"{rec['arch']}|{rec['shape']}|{rec['mesh']}|{rec.get('variant','baseline')}"
    data[key] = rec
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
    os.replace(tmp, out_path)


def _run_all(meshes: list[str], out_path: str, variant: str,
             only_missing: bool, timeout: int, jobs: int = 1) -> int:
    import threading

    from repro.configs import all_cells
    existing = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            existing = json.load(f)
    todo = []
    for mesh_name in meshes:
        for arch, shape in all_cells():
            key = f"{arch}|{shape}|{'2x16x16' if mesh_name=='multi' else '16x16'}|{variant}"
            if only_missing and existing.get(key, {}).get("status") in ("OK", "SKIP"):
                continue
            todo.append((key, arch, shape, mesh_name))

    lock = threading.Lock()
    failures = [0]

    def worker():
        while True:
            with lock:
                if not todo:
                    return
                key, arch, shape, mesh_name = todo.pop(0)
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh_name,
                   "--variant", variant, "--out", out_path]
            print(f"[dryrun] {key} ...", flush=True)
            t0 = time.time()
            mesh_tag = "2x16x16" if mesh_name == "multi" else "16x16"
            try:
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=timeout)
            except subprocess.TimeoutExpired:
                print(f"  {key} TIMEOUT after {timeout}s", flush=True)
                with lock:
                    _merge_out(out_path, {"arch": arch, "shape": shape,
                                          "variant": variant, "mesh": mesh_tag,
                                          "status": "TIMEOUT"})
                    failures[0] += 1
                continue
            dt = time.time() - t0
            if r.returncode != 0:
                tail = (r.stderr or r.stdout).strip().splitlines()[-12:]
                print(f"  {key} FAIL ({dt:.0f}s):\n    "
                      + "\n    ".join(tail), flush=True)
                with lock:
                    # single-cell invocations merge their own record (incl.
                    # python-level errors); only fill in hard crashes
                    data = {}
                    if os.path.exists(out_path):
                        with open(out_path) as f:
                            data = json.load(f)
                    if data.get(key, {}).get("status") not in ("FAIL",):
                        _merge_out(out_path, {"arch": arch, "shape": shape,
                                              "variant": variant,
                                              "mesh": mesh_tag,
                                              "status": "FAIL",
                                              "error": "\n".join(tail)})
                    failures[0] += 1
            else:
                print(f"  {key} ok ({dt:.0f}s)", flush=True)

    threads = [threading.Thread(target=worker) for _ in range(max(1, jobs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return failures[0]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--only-missing", action="store_true")
    ap.add_argument("--timeout", type=int, default=2400)
    ap.add_argument("--jobs", type=int, default=1)
    args = ap.parse_args()

    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]
    if args.all:
        sys.exit(1 if _run_all(meshes, args.out, args.variant,
                               args.only_missing, args.timeout,
                               args.jobs) else 0)

    assert args.arch and args.shape, "--arch/--shape required without --all"
    for mesh_name in meshes:
        rec = None
        try:
            rec = run_cell(args.arch, args.shape, mesh_name == "multi",
                           args.variant)
        except Exception:
            traceback.print_exc()
            rec = {"arch": args.arch, "shape": args.shape,
                   "variant": args.variant,
                   "mesh": "2x16x16" if mesh_name == "multi" else "16x16",
                   "status": "FAIL", "error": traceback.format_exc()[-2000:]}
        _merge_out(args.out, rec)
        status = rec.get("status")
        print(json.dumps({k: v for k, v in rec.items()
                          if k in ("arch", "shape", "mesh", "status",
                                   "lower_s", "compile_s", "skip_reason")}))
        if status == "FAIL":
            sys.exit(1)


if __name__ == "__main__":
    main()
