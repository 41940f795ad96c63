"""``step_hbm_gb`` of a decoder cell without the chip: the cell's step
program (the Solver's ``fused``, with its compiler option) compiled for a
v5e that is described and not attached, from shapes alone; nothing runs.
PR 36 read 11.513285, 11.737186 and 16.480480 GB here and the same digits
on the chip, so a change to a step's memory can be found, and bisected,
at three to six minutes a compile and no chip time.

    JAX_PLATFORMS=cpu python scripts/step_hbm.py mellum_train_packed8k [HLO.txt]

from the root of a checkout (a second one under ``runs/`` reads the
parent); one process at a time may describe the chip.  The cells of
``apps/lm_app`` only."""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main(argv) -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import run
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.solver.trainer import init_opt_state, make_train_step
    from sparknet_tpu.utils import profiling

    jax.config.update("jax_enable_compilation_cache", False)
    cell = run.load_cell(argv[0])
    args = lm_app.parser().parse_args(
        [*cell["config"]["argv"], *cell["traffic"]["argv"], "--seed", "1"]
    )
    cfg = lm_app.make_config(args)
    shapes = {"input_ids": (args.batch_size, args.seq_len)}
    if args.pack_documents:
        shapes.update(segment_ids=shapes["input_ids"], positions=shapes["input_ids"])
    model = lm_app.model_class(cfg)(
        cfg, shapes, compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        attention_impl="flash",  # as a TPU takes them: the backend here is the CPU
    )
    solver = lm_app.make_solver_param(args)
    train_step = make_train_step(model, solver, None)

    def fused(params, state, opt_state, batch, it, rng):  # Solver._finish_init's
        with profiling.scope("rng"):
            rng, step_rng = jax.random.split(rng)
        params, state, opt_state, metrics = train_step(
            params, state, opt_state, batch, it, step_rng
        )
        return params, state, opt_state, it + 1, rng, metrics

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    put = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree
    )
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(lambda p: init_opt_state(solver, p), params)
    batch = {k: jax.ShapeDtypeStruct(v, jnp.int32) for k, v in shapes.items()}
    batch["labels"] = batch["input_ids"]
    compiled = jax.jit(
        fused, donate_argnums=(0, 1, 2, 4, 5),
        compiler_options={"xla_tpu_scoped_vmem_limit_kib": "32768"},
    ).lower(
        put(params), put(state), put(opt_state), put(batch),
        put(jax.ShapeDtypeStruct((), jnp.int32)),
        put(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
    ).compile()
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        - m.alias_size_in_bytes + m.temp_size_in_bytes
    )
    print(
        f"step_hbm: {argv[0]} in {os.getcwd()}: {total / 1e9:.9f} GB "
        f"(arguments {m.argument_size_in_bytes / 1e9:.4f}, temporaries "
        f"{m.temp_size_in_bytes / 1e9:.4f}); compiled for a described chip, not run"
    )
    if len(argv) > 1:
        with open(argv[1], "w") as f:
            f.write(compiled.as_text())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
