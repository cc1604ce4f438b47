"""Compare the pre-copy's dirty-scan programs of two checkouts.

Lowers ``precopy._leaf_dirty`` (block 16,384) for a described TPU v5e
chip, for every leaf shape of the two replica cells that the scan cannot
read in place and for the dense replica's head and KV cache, and prints
per leaf: its Pallas kernels, the instructions that write an array as
large as the leaf, the compiled temp bytes, and a digest of the compiled
program without source locations (metadata and stack frames dropped, each
Mosaic kernel body printed without debug info).

    JAX_PLATFORMS=cpu python tests/compare_leaf_dirty_programs.py A [B]

``A`` and ``B`` are checkouts of this repository. With one, its table is
printed; with two, each is lowered in a process of its own and the script
exits 1 if any leaf differs. Needs no chip; takes under a minute.
"""
import base64
import hashlib
import json
import math
import os
import re
import subprocess
import sys

BLOCK = 16384
CELLS = ("replica-granite-4h-micro", "replica-internlm2-1p8b")


def _leaves(repo, cell):
    import jax
    from repro.models import lm
    cfg = json.load(open(os.path.join(repo, "bench/configs", cell + ".json")))
    if cfg.get("driver") == "precopy_hybrid":
        from bench.drivers.precopy_hybrid import arch_of
        arch = arch_of(cfg)
    else:
        from bench.drivers.precopy import arch_of
        arch = arch_of(cfg["name"], cfg["model"])
    s = cfg["serving"]
    tree = jax.eval_shape(lambda: {
        "params": lm.init_params(arch, jax.random.key(0)),
        "cache": lm.init_cache(arch, s["batch"], s["cache_len"])})
    return jax.tree_util.tree_flatten_with_path(tree)[0]


def _digest(text):
    from jax._src.lib.mlir import ir

    def body(m):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            mod = ir.Module.parse(base64.b64decode(m.group(1)))
            return '"body":' + json.dumps(
                mod.operation.get_asm(enable_debug_info=False))

    text = text[re.search(r"^(%|ENTRY)", text, re.M).start():]
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r", ?stack_frame_id=\d+", "", text)
    text = re.sub(r'"body":"([^"]+)"', body, text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def lower(repo):
    """{cell:leaf -> fingerprint} of ``repo``'s ``_leaf_dirty``."""
    sys.path[:0] = [os.path.join(repo, "src"), repo]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.core import precopy
    from repro.kernels import backend, dirty_delta

    jax.config.update("jax_enable_compilation_cache", False)
    backend.resolve_interpret = lambda *args: False    # lower for the chip
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])
    out = {}
    for cell in CELLS:
        seen = set()
        for path, a in _leaves(repo, cell):
            name = jax.tree_util.keystr(path)
            planned = (jnp.issubdtype(a.dtype, jnp.floating) and
                       dirty_delta.plan(a.shape, a.dtype, BLOCK) is not None)
            dense = cell.startswith("replica-internlm2") and (
                name.endswith("['head']") or name.endswith("['k']"))
            if (a.shape, a.dtype) in seen or (planned and not dense):
                continue
            seen.add((a.shape, a.dtype))
            spec = jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
            c = precopy._leaf_dirty.lower(spec, spec, BLOCK).compile()
            text = c.as_text()
            writes = re.findall(r"= \w+\[([\d,]*)\]\S* ([\w-]+)\(", text)
            out[f"{cell}:{name}"] = {
                "shape": list(a.shape), "dtype": str(a.dtype),
                "kernels": sorted(set(re.findall(
                    r"%(\w+?)(?:\.\d+)? = [^\n]*tpu_custom_call", text))),
                "full_leaf_copies": sorted(
                    op for dims, op in writes
                    if op not in ("parameter", "bitcast") and math.prod(
                        int(d) for d in dims.split(",") if d)
                    >= math.prod(a.shape)),
                "temp_bytes": c.memory_analysis().temp_size_in_bytes,
                "digest": _digest(text)}
    return out


def _run(repo):
    p = subprocess.run([sys.executable, __file__, "--json", repo],
                       capture_output=True, text=True, check=True)
    return json.loads(p.stdout.splitlines()[-1])


def main(argv):
    if argv[0] == "--json":
        print(json.dumps(lower(os.path.abspath(argv[1]))))
        return 0
    runs = [_run(os.path.abspath(r)) for r in argv]
    differ = 0
    for leaf, first in runs[0].items():
        same = all(r.get(leaf) == first for r in runs[1:])
        differ += not same
        print("same " if same else "DIFF ", leaf, json.dumps(
            {k: first[k] for k in ("dtype", "shape", "kernels",
                                   "temp_bytes", "digest")}),
              len(first["full_leaf_copies"]), "full-leaf writes")
    return 1 if differ or any(set(r) != set(runs[0]) for r in runs) else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
