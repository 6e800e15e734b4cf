"""What the readers of ling-3.0-flash-vl-ep64's cell share: whether the
built step is one with Kimi-Delta-Attention layers. ``gmm_ms_per_step``
and ``moe_load_max_over_mean`` list their cells by name; the readers
beside this file are their arithmetic under names that list this cell.
The grouped products' rooflines (``_gmm.roofline``) are NOT read here:
their arithmetic takes the rows a call multiplies from the windows'
mean, and this cell's routing is bursty (PERF.md, Open questions), so
the traced steps' rows are not the windows' mean and the share read
anything from 37 to 158%."""

import events as ev


def built_with_kda(run):
    """True where ``step_built`` names ``kda_layers``; False in every
    other cell and on the parent commit."""
    built = ev.of_kind(run["events"], "step_built")
    return bool(built and built[0].get("kda_layers"))
