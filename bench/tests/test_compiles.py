"""The compile meter counts and names what compiles after its mark."""
import compiles


def test_a_new_program_is_counted_and_named():
    import jax
    import jax.numpy as jnp

    meter = compiles.CompileMeter()

    @jax.jit
    def bench_probe_fn(x):
        return x * 3 + 1

    x = jnp.arange(7)
    mark = meter.mark()
    bench_probe_fn(x).block_until_ready()
    got = meter.since(mark)
    assert got["lowered"] == 1 and "bench_probe_fn" in got["names"][0]
    mark = meter.mark()
    bench_probe_fn(x).block_until_ready()  # cached: nothing new
    assert meter.since(mark)["lowered"] == 0
