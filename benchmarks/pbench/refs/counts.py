"""Every Count the traffic can ask of a dense frame, tabulated."""

from ..reference import CountReference

slice_part, assemble = CountReference.slice_part, CountReference.assemble
