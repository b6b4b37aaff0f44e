"""trino_tpu — a TPU-native distributed SQL analytics engine.

A from-scratch re-design of the capabilities of the reference Trino engine
(/root/reference, Java MPP SQL engine) for TPU hardware: columnar pages as
device arrays, expression bytecode-codegen replaced by jax tracing + XLA
compilation, HTTP page shuffle replaced by XLA collectives over an ICI mesh,
with a host-side async control plane.

Layer map (mirrors SURVEY.md §1):
  types.py / page.py        — type system + columnar Page/Block model
  expr/                     — typed expression IR + jax lowering (codegen slot)
  sql/                      — lexer/parser/analyzer (SQL frontend)
  plan/                     — logical plan nodes, optimizer, fragmenter
  ops/                      — physical operators as jax kernels
  exec/                     — local execution: fragment -> jitted pipeline
  parallel/                 — mesh, collectives, distributed exchanges
  connectors/               — tpch generator, memory, blackhole + SPI
  server/ client/           — coordinator/worker control plane + protocol
"""

__version__ = "0.1.0"


def force_cpu(n_devices: int = 8) -> None:
    """Test/dev mode: the CPU backend with n virtual devices and x64 on,
    so sharding and collective paths run without an accelerator.  Call it
    before any jax backend initializes.  Plain CPU runs need only
    ``JAX_PLATFORMS=cpu``, which jax honours as set."""
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    opt = f"--xla_force_host_platform_device_count={n_devices}"
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {opt}".strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)


def enable_x64() -> None:
    import jax

    jax.config.update("jax_enable_x64", True)
