"""Compile a cell's step program for a v5e WITHOUT a chip.

    JAX_PLATFORMS=cpu TPU_WORKER_HOSTNAMES=localhost \
        python3 benchmark/rehearse.py --workload lm110m_s512_dp4

libtpu's compiler is installed in the sandbox, and
``jax.experimental.topologies.get_topology_desc("v5e:2x2")`` describes
chips that are not attached. The cell's workflow is built at its real
size through the program's own entry points (``Main.setup_config`` /
``Main.load``), initialized on a device object that says what the chip
would (platform ``tpu``, bf16 policy, the described chips), and its
epoch program is lowered on shapes and compiled by the real TPU
compiler, Mosaic kernels and GSPMD partitioning included. Printed:
``memory_analysis()`` (bytes on each chip), the collectives and the
``tpu_custom_call`` count of the optimized HLO, and the compile time.

This is how a batch is checked against 16 GB and a partitioning against
the compiler before chip time is spent. Nothing runs, so nothing here is
a time or a result. The recipe is the verify skill's; it reaches for
``XLAStep._epoch_program``, which the measuring path never does.
"""

import argparse
import json
import os
import re
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)

COLLECTIVE = re.compile(
    r"= \S+ (all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")


def described_device(chips):
    """An ``XLADevice`` that answers as ``chips`` v5e chips would."""
    import jax.numpy as jnp
    from jax.experimental import topologies
    from veles import backends
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    device = backends.XLADevice(platform="cpu")
    device.platform = "tpu"
    device.compute_dtype = device.act_dtype = jnp.bfloat16
    device.jax_devices = list(topo.devices)[:chips]
    # the Pallas kernels size their VMEM grant from the ATTACHED chip
    # (pltpu.get_tpu_info), which here is the CPU: say what a v5e has,
    # as tests/test_chip_smoke.py does
    from veles.znicz_tpu.parallel import pallas_attention
    pallas_attention._device_vmem_bytes = lambda: 128 << 20
    return device


def shapes_for_described_devices():
    """``jax.device_put`` onto a described chip cannot hold an array:
    give back the shape with that placement instead."""
    import jax
    import numpy
    real_put = jax.device_put
    attached = set(jax.devices())

    def put(x, device=None, *args, **kwargs):
        devices = getattr(device, "device_set", None)
        if devices and not devices <= attached:
            x = numpy.asarray(x) if not hasattr(x, "dtype") else x
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=device)
        return real_put(x, device, *args, **kwargs)

    jax.device_put = put


def as_shapes(args, device):
    """Every array of ``args`` as a shape placed on the described
    chips: what was sharded keeps its sharding, the rest is put on the
    one chip or replicated over the mesh."""
    import jax
    from jax.sharding import (NamedSharding, PartitionSpec,
                              SingleDeviceSharding)
    if device.mesh is not None:
        default = NamedSharding(device.mesh, PartitionSpec())
    else:
        default = SingleDeviceSharding(device.jax_devices[0])

    def shape(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        x = jax.numpy.asarray(x) if not hasattr(x, "dtype") else x
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=default)

    return jax.tree_util.tree_map(shape, args)


def rehearse(workload, traffic=None):
    """``traffic``: numbers to try in place of the traffic file's (a
    batch, a sequence length) before one is written there."""
    sys.path.insert(0, CHECKOUT)
    os.chdir(CHECKOUT)
    import jax
    from benchmark import run
    from benchmark.drivers import train
    from veles.__main__ import Main, import_file
    cell = run.resolve(BENCH_DIR, workload)
    cell["traffic"].update(traffic or {})
    main = Main(train.build_argv(cell, seed=1, platform="tpu"))
    module = import_file(main.args.workflow, "veles_workflow_module")
    main.setup_config()
    module.run(main.load, lambda **kwargs: None)    # build, do not launch
    device = described_device(cell["chips"])
    jax.config.update("jax_enable_compilation_cache", False)
    shapes_for_described_devices()
    t0 = time.perf_counter()
    main.workflow.initialize(device=device)
    t_init = time.perf_counter() - t0
    step = main.workflow.xla_step
    fn, args, _, serves, _ = step._epoch_program(1)
    t0 = time.perf_counter()
    compiled = fn.lower(*as_shapes(args, device)).compile()
    t_compile = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    collectives = {}
    for name in COLLECTIVE.findall(hlo):
        collectives[name] = collectives.get(name, 0) + 1
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "alias_size_in_bytes", "temp_size_in_bytes",
              "generated_code_size_in_bytes")
    sizes = {f: int(getattr(mem, f)) for f in fields}
    live = sizes["argument_size_in_bytes"] + sizes["output_size_in_bytes"] \
        - sizes["alias_size_in_bytes"] + sizes["temp_size_in_bytes"]
    report = {
        "workload": workload, "chips": cell["chips"],
        "tried": traffic or {},
        "compiled_for": str(device.jax_devices[0].device_kind),
        "steps_per_epoch_program": serves,
        "memory_analysis": sizes,
        "bytes_live_per_chip": live,
        "share_of_16e9": round(live / 16e9, 4),
        "tpu_custom_calls": hlo.count('custom_call_target="tpu_custom_call"'),
        "collectives": collectives,
        "host_init_s": round(t_init, 1),
        "compile_s": round(t_compile, 1),
    }
    print(json.dumps(report, indent=1), flush=True)
    return report


if __name__ == "__main__":
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--set", action="append", default=[],
                   metavar="KEY=NUMBER",
                   help="try this traffic number instead of the file's")
    args = p.parse_args()
    rehearse(args.workload,
             {k: json.loads(v) for k, v in
              (item.split("=", 1) for item in args.set)})
