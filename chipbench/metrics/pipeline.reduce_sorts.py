"""Reduce stages of the cell's program that sort their input: the program
counter `reduce.sort` while the cell's flow is planned, compiled and traced
once more, as `paths/pipeline.py` plans and compiles it, on sources of the
shapes `bind_device` gives the configuration's row counts.  Tracing makes
no XLA compile.  A Reduce whose input order covers its key counts
`reduce.sorted` instead, so a lost order elision raises this count.  A
program without the counters reads nothing."""


def read(ctx):
    import jax

    from repro import obs
    from repro.core import masked as M
    from repro.core.operators import Source
    from repro.core.optimizer import optimize
    from repro.core.pipeline import ExecutableCache

    r = ctx.run
    obs.reset()
    obs.enable()
    try:
        cp = optimize(r.flows.flow(r.config)).compile(
            cache=ExecutableCache())
        shapes = {}
        for src in cp.flow.iter_nodes():
            if not isinstance(src, Source) or src.name in shapes:
                continue
            cap = M.bucket_capacity(max(src.num_records, 1))
            fields = src.out_schema.fields
            cols = {f: jax.ShapeDtypeStruct(
                (cap,), jax.dtypes.canonicalize_dtype(src.out_schema.dtype(f)))
                for f in fields}
            order = M.order_prefix(src.sorted_on or (), fields) \
                if cp.use_order else ()
            shapes[src.name] = M.MaskedBatch(
                cols, jax.ShapeDtypeStruct((cap,), bool), order)
        masked, sig = cp._masked_sig(shapes)
        jax.eval_shape(cp._executable(sig), masked)
    finally:
        obs.disable()
        counts = obs.snapshot()["counts"]
        obs.reset()
    if "reduce.sort" not in counts and "reduce.sorted" not in counts:
        return None
    return counts.get("reduce.sort", 0)
