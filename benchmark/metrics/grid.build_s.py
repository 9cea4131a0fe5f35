"""Host seconds of ``Grid()...initialize()`` and any refinement (set-up
part ``grid_build``, the harness's clock)."""


def read(ctx):
    return ctx.setup.get("grid_build")
