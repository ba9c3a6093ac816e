"""Work of one request of the PFB power pipeline in the int8 tier: the
algorithm's count, the same as ``pfb_power``'s, so that
``plan_roofline_pct`` puts both tiers on one yardstick."""
from __future__ import annotations

from bench.work.pfb_power import work

__all__ = ["work"]
