"""Control (``make_train_step``): the fused in-jit step on the same
model and batch, timed after the window in the traced run of a PS cell.
PS/fused is ``step_ms`` over this."""

METRICS = {"control.fused_step_ms": lambda ctx: ctx.control_step_ms}
