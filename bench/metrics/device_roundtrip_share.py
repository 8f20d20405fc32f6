"""Share of the traced part of the window in the host-to-device copies, the
dispatch of the jitted fixed point and the wait for its outputs
(``accel.jax.put``, ``.run`` and ``.fetch`` spans), in percent.
``match_share`` less this share is the host's packing and live-subset
gather."""

from bench.spanclock import span_share


def read(ctx):
    return span_share(ctx, {"accel.jax.put", "accel.jax.run",
                            "accel.jax.fetch"})
