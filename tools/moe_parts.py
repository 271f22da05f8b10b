"""The expert layers' device ms a program, split by phase (fwd, recompute,
bwd), part (router, dispatch, experts, combine) and kind of work (a Mosaic
call, an instruction inside a branch of a rung's `lax.switch`, anything
else by its primitive), from the trace and HLO a `--trace 1` run of a
sparse cell left under benchmark/.out/<cell> (needs no chip). `--dump DIR`
also leaves the reduced trace as json beside the step's text, small enough
to bring back from the chip's machine; `--from DIR` reads that.

    python3 tools/moe_parts.py benchmark/.out/<cell> [--dump DIR]
    python3 tools/moe_parts.py --from DIR
"""
import os, re, sys, json, gzip, glob, shutil, collections
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
import scopes, trace_reduce, kernels
if sys.argv[1] == "--from":
    d = sys.argv[2]
    tr = json.load(open(os.path.join(d, "trace.json")))
    hlo = os.path.join(d, "hlo")
else:
    out = sys.argv[1]
    hlo, tr = os.path.join(out, "hlo"), trace_reduce.reduce(os.path.join(out, "trace"))
    if "--dump" in sys.argv:
        d = sys.argv[sys.argv.index("--dump") + 1]
        os.makedirs(os.path.join(d, "hlo"), exist_ok=True)
        json.dump({k: tr[k] for k in ("self_s", "calls", "busy_s", "window_s")}, open(os.path.join(d, "trace.json"), "w"))
        for p in glob.glob(os.path.join(hlo, "step_*.hlo.txt")):
            shutil.copy(p, os.path.join(d, "hlo"))
table = scopes.instructions(hlo)
n = scopes.programs_run(tr, hlo) or 1
mosaic = kernels.mosaic_calls(hlo)
text = open(scopes.texts(hlo, "step")[0]).read()
opn = dict(re.findall(r'^\s+(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?op_name="([^"]*)"', text, re.M))
acc = collections.defaultdict(lambda: [0.0, 0])
tot = collections.defaultdict(float)
for name, t in tr["self_s"].items():
    i = table.get(name)
    if not i or not i["path"] or "moe" not in i["path"]:
        continue
    path = list(i["path"])
    phase = "recompute" if path[0] == "recompute" else i["phase"]
    part = next((c for c in path[path.index("moe"):] if c in ("router", "dispatch", "experts", "combine")), "?")
    branch = next((c for c in path if c.startswith("branch_")), None)
    prim = opn.get(name, "").split(";")[0].rsplit("/", 1)[-1]
    if name in mosaic:
        kind = "mosaic"
    elif branch or i["opcode"] == "conditional":
        kind = "row-major " + (branch or "cond") + " " + (i["opcode"] if i["opcode"] != "fusion" else "fusion(" + prim + ")")
    else:
        kind = "outside:" + (i["opcode"] if i["opcode"] != "fusion" else "fusion(" + prim + ")")
    acc[(phase, part, kind)][0] += t
    acc[(phase, part, kind)][1] += tr["calls"].get(name, 0)
    tot["mosaic" if kind == "mosaic" else "row-major" if kind.startswith("row") else "sort" if "sort" in prim or "sort" in kind or i["opcode"] == "sort" else "other"] += t
print(f"{n} programs; busy {1e3 * tr['busy_s'] / n:.3f} ms a program; moe total {1e3 * sum(tot.values()) / n:.3f}")
print({k: round(1e3 * v / n, 3) for k, v in tot.items()})
for (phase, part, kind), (t, c) in sorted(acc.items(), key=lambda kv: -kv[1][0])[:140]:
    print(f"{1e3 * t / n:9.3f} {c:6d}  {phase:9s} {part:9s} {kind}")
