"""Host seconds of the model's construction and its initial state (set-up
parts ``model_init`` and ``initial_state``, the harness's clock)."""


def read(ctx):
    parts = [ctx.setup.get(k) for k in ("model_init", "initial_state")]
    parts = [p for p in parts if p is not None]
    return sum(parts) if parts else None
