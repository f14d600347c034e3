"""compile_s: seconds from the start of lowering the step to its first
ready result (tracing, lowering, the compile or its load from the
persistent cache, the first step). Host clock."""


def read(trace, host, cell):
    return host.get("compile_s")
