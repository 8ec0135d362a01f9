"""Set-up seconds in JAX tracing and lowering: the union of the program's
logged jaxpr-trace and MLIR-lowering spans up to the readings, which
come after the window and before the reference compiles anything. With
`retraces` at 0, all of it is set-up. A persistent-cache hit still pays
this."""
from chipbench import compiles


def read(ctx):
    return compiles.union_s((compiles.TRACE, compiles.LOWER))
