"""Rehearsal without a chip: compile each cell's real step, at its real
sizes, for a described ``v5e:2x2`` topology, and print what the TPU's
compiler counts for it.

    JAX_PLATFORMS=cpu python3 chipbench/aot_check.py [--workload <cell>] [--set config.sizes.n_layer=12 ...]

What the compiler refuses here (a program that does not fit 16 GB, a
kernel it cannot tile) costs no chip time. Nothing runs, so this says
nothing about results or speed. The bytes it prints are recorded in each
configuration file under ``compiler_memory``. ``--set`` tries a size
that is not in the files (a deeper model, a larger batch) before it is
written there.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TOPOLOGY = "v5e:2x2"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append",
                    help="a cell of BENCHMARK.json (default: every cell)")
    ap.add_argument("--set", action="append", default=[],
                    metavar="config.KEY.KEY=JSON | workload.KEY=JSON")
    ap.add_argument("--hlo", default=None, metavar="DIR",
                    help="also write each compiled module's text here")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:] = [ROOT] + [p for p in sys.path
                            if os.path.abspath(p or ".") not in (HERE, ROOT)]
    import jax
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from chipbench import device, run

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: leave the cache off
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=TOPOLOGY)
    bench = run.load_json(ROOT, "BENCHMARK.json")
    for name in args.workload or [w["name"] for w in bench["workloads"]]:
        entry, config, workload = run.load_cell(bench, name)
        for item in args.set:
            path, _, value = item.partition("=")
            target, *keys = path.split(".")
            node = {"config": config, "workload": workload}[target]
            for k in keys[:-1]:
                node = node[k]
            node[keys[-1]] = json.loads(value)
        chips = entry["chips"]
        mesh = Mesh(topo.devices[:chips], ("hvd",))
        family = run.load_module("families", config["family"])
        step, shapes = family.abstract_step(config, workload, chips=chips,
                                            mesh=mesh)
        t0 = time.perf_counter()
        compiled = step.lower(*shapes).compile()
        print(json.dumps({
            "workload": name, "topology": TOPOLOGY, "chips": chips,
            "set": args.set, "compile_s_here": time.perf_counter() - t0,
            **device.program_memory(compiled)}), flush=True)
        if args.hlo:
            os.makedirs(args.hlo, exist_ok=True)
            with open(os.path.join(args.hlo, name + ".hlo.txt"), "w") as f:
                f.write(compiled.as_text())
    return 0


if __name__ == "__main__":
    sys.exit(main())
