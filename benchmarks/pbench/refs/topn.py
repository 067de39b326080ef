"""|row| and |row & src| under writes, rankings judged by subsets."""

from ..reference import TopNReference

slice_part, assemble = TopNReference.slice_part, TopNReference.assemble
