"""AOI (area-of-interest) managers.

The seam mirrors the reference's ``aoi.AOIManager`` interface
(Space.go:33,105: Enter/Leave/Moved + OnEnterAOI/OnLeaveAOI callbacks on
entities). Two implementations:

- ``XZListAOIManager`` — CPU sweep-list, per-space, synchronous callbacks
  (reimplementation of the go-aoi XZList idea, SURVEY.md §2.4).
- ``BatchAOIService`` + ``BatchSpaceAOIManager`` — the TPU path: all spaces'
  positions batched into one NeighborEngine launch per tick; enter/leave
  diffs delivered at tick boundaries (SURVEY.md §7.1).

The batched path lives in ``goworld_tpu.entity.aoi.batched`` and is not
imported here: it loads JAX, which an xzlist game never needs (importing
it inside the dispatch that creates the first AOI space froze the loop
for seconds).
"""

from goworld_tpu.entity.aoi.base import AOIManagerBase
from goworld_tpu.entity.aoi.xzlist import XZListAOIManager

__all__ = ["AOIManagerBase", "XZListAOIManager"]
