#!/usr/bin/env python3
"""Compile a training cell's step at its real size for a described v5e.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse_compile.py internlm2_train4k [depth [minibatch]]

No chip is needed and none is used: the TPU's compiler is installed here
and compiles for a topology that is described, not attached
(`on-chip-measurement` guide, section 2). The workflow is built and
initialized on the CPU exactly as the child builds it, then
``TrainStep._train_step_fn`` is lowered with abstract arguments placed on
the described device. The program asks ``jax.default_backend()`` where it
chooses the flash kernel, so this script, and only it, answers "tpu" while
the step is traced. Prints ``memory_analysis()`` and the compile seconds; a
refusal by the compiler ("Ran out of memory in memory space hbm") is the
exception it raises, and the largest depth that it takes is the cell's
(internlm2-1.8b at minibatch 1: 14.94 of 15.75 GiB at depth 8, refused at
9). Nothing here runs, so nothing here is a measurement.
"""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def main(argv):
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from veles_tpu.backends import XLADevice
    from veles_tpu.config import root
    from chipbench import model_file
    name = argv[1] if len(argv) > 1 else "internlm2_train4k"
    with open(os.path.join(HERE, "workloads", name + ".json")) as f:
        wl = json.load(f)
    with open(os.path.join(HERE, "configs", wl["config"] + ".json")) as f:
        cfg = json.load(f)
    if len(argv) > 3:
        wl["minibatch"] = int(argv[3])
    if len(argv) > 2:
        cfg["num_hidden_layers"] = int(argv[2])
    root.common.engine.mixed_precision = "--mixed-precision" in wl["cli"]
    model_file.skip_host_draw()
    wf = model_file.build_workflow(cfg, wl, seed=1)
    wf.initialize(device=XLADevice("cpu", mesh_axes={"data": 1}))
    step, loader = wf.train_step, wf.loader
    loader.run()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)
    dataset, labels, targets, indices, mask = step._inputs()
    args = abstract((step.params, step.opt_state,
                     step._make_zero_accum(mon=True), dataset, labels,
                     targets, indices, mask,
                     jax.numpy.float32(1.0), step._rng.jax_key()))
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        t = time.time()
        lowered = jax.jit(step._train_step_fn,
                          donate_argnums=(0, 1, 2)).lower(*args)
        t_lower = time.time() - t
    finally:
        jax.default_backend = real_backend
    t = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    print("cell %s, depth %d, minibatch %d, T %d" % (
        name, cfg["num_hidden_layers"], wl["minibatch"], wl["seq_len"]))
    print("traced and lowered in %.1f s, compiled for v5e in %.1f s; "
          "%d tpu_custom_call(s) in the program"
          % (t_lower, t_compile, text.count('"tpu_custom_call"')))
    print("memory_analysis: arguments %.2f GB, outputs %.2f GB, aliased "
          "%.2f GB, temporaries %.2f GB, code %.3f GB" % tuple(
              x / 1e9 for x in (mem.argument_size_in_bytes,
                                mem.output_size_in_bytes,
                                mem.alias_size_in_bytes,
                                mem.temp_size_in_bytes,
                                mem.generated_code_size_in_bytes)))
    print("arguments + temporaries + outputs - aliased = %.2f GB" % (
        (mem.argument_size_in_bytes + mem.temp_size_in_bytes
         + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 1e9))


if __name__ == "__main__":
    main(sys.argv)
