"""The least operations and bytes each operation family needs, from its
shapes: one family per module.  Recomputation (remat) is never counted."""
