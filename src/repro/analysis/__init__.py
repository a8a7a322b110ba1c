"""Modeled performance of stencil plans (:mod:`.stencil_roofline`)."""
