"""Device milliseconds an optimizer step spends around the routed layers'
kernels: the router (``fedml.router``), the plan's sorts and index work
(``fedml.experts.plan``) and XLA's passes over rows between the kernels
(``fedml.experts.layout``), all directions; not the products
(``moe_experts_ms_per_step``) nor the combine (``moe_combine_ms_per_step``).
As ``attn_bwd_ms_per_step`` reads its own."""

from chipbench.harness import scopes


def read(run):
    return scopes.epoch_ms_per_step(run, (
        "fedml.router", "fedml.experts.plan", "fedml.experts.layout"))
