"""Set-up seconds in XLA compiles or persistent-cache loads: the union of
the program's logged backend-compile spans up to the readings (after the
window, before the reference compiles anything)."""
from chipbench import compiles


def read(ctx):
    return compiles.union_s((compiles.COMPILE,))
