"""Asserted once before a several-DataNode cell's window: lineitem's shards
sit on as many distinct devices as there are DataNodes, and a compiled mesh
program holds an all-to-all (copied from chip_smoke.py phase_mesh4).  The
programs are captured by the mesh tier's EXPORT_HOOK, armed before
warm-up."""

PROGRAMS = {}     # id(fn) -> (jitted shard_map program, arg shapes)


def _capture(_tag, fn, args):
    import jax

    def shape_of(a):
        if not isinstance(a, jax.Array):
            return a
        if len(a.sharding.device_set) > 1:      # a staged, sharded column
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=a.sharding)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    if id(fn) not in PROGRAMS:
        PROGRAMS[id(fn)] = (fn, tuple(shape_of(a) for a in args))


def arm():
    from opentenbase_tpu.exec import mesh_exec
    mesh_exec.EXPORT_HOOK = _capture


def problems(stack, n_datanodes):
    from opentenbase_tpu.exec.mesh_exec import mesh_runner_for
    from opentenbase_tpu.storage.bufferpool import POOL
    out = []
    runner = mesh_runner_for(stack.cluster)
    ent = POOL.mesh_peek(runner, "lineitem") if runner is not None else None
    if ent is None:
        out.append("mesh: lineitem is not staged for the mesh runner")
    else:
        for arr in ent.staged.arrs.values():
            devs = {str(s.device) for s in arr.addressable_shards}
            if len(devs) != n_datanodes:
                out.append(f"mesh: lineitem shards on {sorted(devs)}, not "
                           f"{n_datanodes} distinct devices")
                break
    has = ["all-to-all" in fn.lower(*shapes).compile().as_text()
           for fn, shapes in PROGRAMS.values()]
    if not any(has):
        out.append(f"mesh: none of {len(has)} compiled mesh programs "
                   f"contains all-to-all")
    return out
