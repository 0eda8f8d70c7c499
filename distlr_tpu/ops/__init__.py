from distlr_tpu.ops.pallas_lr import (  # noqa: F401
    PanelPlan,
    lr_grad_panels,
    pad_columns,
    panel_plan,
)
