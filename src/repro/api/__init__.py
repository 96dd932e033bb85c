"""`repro.api` — the declarative program layer.

One network definition drives every execution mode:

    from repro.api import get_net
    prog     = get_net("cifar10_tnn")
    params   = prog.init(jax.random.PRNGKey(0))
    deployed = prog.quantize(params)
    logits   = deployed.forward(x)                   # backend="fused" | "ref" | "bitsim"
    report   = deployed.silicon_report(v=0.5)            # paper Table 1 loop

Submodules:
    graph     LayerSpec / CutieGraph + constructor helpers
    quantize  THE quantize->pad->pack path (shared with kernels/ops.py)
    program   CutieProgram / DeployedProgram / StreamSession / SiliconReport
              (the deploy walk itself is `repro.sim.PlanExecutor`)
    registry  register_net / get_net, seeded with the paper's networks

Training these programs is `repro.train` (STE QAT + schedules + the
qat-vs-deployed gap eval); serving many streams is `repro.serving`.  The
full dataflow is drawn in docs/architecture.md.

`kernels/ops.py` imports `repro.api.quantize`, and `api.program` imports the
kernels — so program/registry symbols resolve lazily (PEP 562) to keep the
package import-cycle-free.
"""
from repro.api.graph import (
    CutieGraph,
    LayerSpec,
    conv2d,
    fc,
    flatten,
    global_pool,
    last_step,
    pool,
    tcn,
)
from repro.api import quantize

_PROGRAM = ("CutieProgram", "DeployedProgram", "StreamSession", "SiliconReport",
            "BACKENDS", "SILICON_SOURCES", "check_backend", "export_conv_layers",
            "silicon_report", "silicon_report_from_plan")
_REGISTRY = ("register_net", "get_net", "get_graph", "list_nets",
             "cifar10_tnn_graph", "dvs_cnn_tcn_graph", "cifar10_tnn_wide_graph")

__all__ = [
    "CutieGraph", "LayerSpec", "conv2d", "fc", "flatten", "global_pool",
    "last_step", "pool", "tcn", "quantize", *_PROGRAM, *_REGISTRY,
]


def __getattr__(name):
    if name in _PROGRAM:
        from repro.api import program
        return getattr(program, name)
    if name in _REGISTRY:
        from repro.api import registry
        return getattr(registry, name)
    raise AttributeError(f"module 'repro.api' has no attribute {name!r}")
