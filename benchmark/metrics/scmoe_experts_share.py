"""``scmoe_experts_share``: self time of the routed experts' products (the
operations named in ``program.trace_ops``) over the traced window's busy
time: how much of the device's work the chip's share of the experts is.
The latent family's reader, which reads the configuration's own
``trace_ops``, under this family's name. Reads nothing where no such
operation ran."""

from benchmark.metrics.moe_experts_share import read  # noqa: F401
