"""Share of the traced window the device spent in the unpack program
(``jit__unpack``) that rebuilds each fetched group or page from its staging
words, in %."""
from chipbench.metrics._spans import module_share


def read(run):
    return module_share(run, "serve", "jit__unpack")
