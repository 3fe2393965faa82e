"""Record the scoped-step fixture of `test_bench_spans.py` on a TPU chip.

    python3 bench/tests/record_scoped_step.py OUT_DIR

A toy superstep with the program's scope names (`fwd` under autodiff and
remat, `gossip.pack`, `gossip.mix`, `gossip.unpack`, `consensus_error`,
`optimizer`) over 4 emulated nodes, two remat layers and a scan of 2 rounds,
each part behind an optimization barrier so that no fusion spans two parts,
runs 3 times under the profiler (host tracer level 1: the user spans only)
with the harness's `driver.superstep` span round each call. Writes the trace
gzipped as `v5e_scoped_step.xplane.pb.gz` to OUT_DIR and prints whether the
program the trace holds names the same instructions as the executable that
ran, and the attribution."""
import glob
import gzip
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

NODES, ROUNDS, DIM, ROWS = 4, 2, 1024, 512


def superstep(w, xs):
    import jax
    import jax.numpy as jnp

    barrier = jax.lax.optimization_barrier

    def loss(wn, xn):
        with jax.named_scope("fwd"):
            layer = jax.checkpoint(lambda h: jnp.tanh(h @ wn))
            return jnp.mean(layer(layer(xn)) ** 2)

    def round_(carry, x):
        w, m = carry
        l, g = barrier(jax.vmap(jax.value_and_grad(loss))(w, x))
        with jax.named_scope("gossip.pack"):
            buf = barrier(g.reshape(NODES, -1) * 1.0)
        with jax.named_scope("gossip.mix"):
            buf = barrier((buf + jnp.roll(buf, 1, 0) + jnp.roll(buf, -1, 0)) / 3)
        with jax.named_scope("consensus_error"):
            bar = jnp.mean(buf, axis=0, keepdims=True)
            err = barrier(jnp.max(jnp.linalg.norm(buf - bar, axis=1))
                          / jnp.linalg.norm(bar))
        with jax.named_scope("gossip.unpack"):
            g = barrier(buf.reshape(g.shape) * 1.0)
        with jax.named_scope("optimizer"):
            m = 0.9 * m + 0.1 * g * g
            w = w - 0.01 * g / (jnp.sqrt(m) + 1e-8)
        return (w, m), (jnp.mean(l), err)

    return jax.lax.scan(round_, w, xs)


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from bench import spans

    fn = jax.jit(superstep)
    w = jnp.ones((NODES, DIM, DIM), jnp.float32) / DIM
    w = (w, jnp.zeros_like(w))
    xs = jax.random.normal(jax.random.PRNGKey(0), (ROUNDS, NODES, ROWS, DIM))
    compiled = fn.lower(w, xs).compile()
    ran = compiled.runtime_executable().hlo_modules()[0].as_serialized_hlo_module_proto()
    w, _ = compiled(w, xs)
    jax.block_until_ready(w)
    trace_dir = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    with jax.profiler.trace(trace_dir, profiler_options=options):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("driver.superstep"):
                w, metrics = compiled(w, xs)
                jax.block_until_ready(metrics)
    trace = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, "v5e_scoped_step.xplane.pb.gz")
    with open(trace, "rb") as f, gzip.open(out, "wb") as g:
        g.write(f.read())
    t = spans.load(out)
    same = [spans.scope_map(p).keys() == spans.scope_map(ran).keys()
            for p in t.programs.values()]
    print(json.dumps({"programs": list(t.programs), "same_instructions": same,
                      "bytes": os.path.getsize(out), "summary": spans.summary(t, ROUNDS)}))


if __name__ == "__main__":
    main(sys.argv[1])
