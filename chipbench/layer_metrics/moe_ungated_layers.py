"""How many ``moe_experts`` ops were lowered whose experts are TWO matrices
about a squared ReLU (counter ``ops.moe.ungated_layers``, counted beside
``ops.moe.calls``: once for every trace of such a layer's forward, of which
the op makes one and its grad op another, in each program lowered; it falls
to nothing if a step takes the gated form).  None where the program has no
such counter: the parent of the PR that added it, or a model whose experts
are gated."""


def value(run):
    try:
        from paddle_tpu.fluid import profiler

        found = [v for k, v in profiler.counters().items()
                 if k.startswith("ops.moe.ungated_layers")]
    except Exception:
        return None
    return sum(found) if found else None
