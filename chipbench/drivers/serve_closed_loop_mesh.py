"""Closed-loop serving of a model sharded over a mesh.

The configuration's ``mesh`` (``{"data": d, "model": m}``) is built over
the first ``d * m`` devices, and ``serve_closed_loop``'s own ``run`` (its
loop, timing, sample and check) runs inside ``parallel.ctx.activate`` of
that mesh: ``launch.serve.Server`` takes the active mesh when it is
built, makes its weights sharded and serves under it. A program whose
``Server`` takes no mesh would put the whole model on one device; the
run ends at once with an error instead.
"""
from __future__ import annotations

import inspect

from chipbench import bench

_closed_loop = bench.driver("serve_closed_loop")


def run(ctx: bench.RunContext) -> bench.DriverResult:
    from repro.launch.serve import Server
    if "mesh" not in inspect.signature(Server).parameters:
        raise bench.BenchError("launch.serve.Server takes no mesh: the "
                               "cell's model cannot be served sharded")
    from repro.launch.mesh import make_local_mesh
    from repro.parallel import ctx as parallel_ctx
    shape = ctx.config["mesh"]
    mesh = make_local_mesh(shape["data"], shape["model"])
    ctx.notes["mesh"] = dict(mesh.shape)
    with parallel_ctx.activate(mesh):
        return _closed_loop.run(ctx)
