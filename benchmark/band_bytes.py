"""The bytes a band kernel call really moves between HBM and VMEM, which
is more than ``bytes_model.py``'s least: each band reads its halo rows
again.

The generic 2D band engine (``ops/pallas_generic.py``) cuts the domain
into ``bands`` bands of ``band_rows`` rows.  For one band, one call
copies in ``band_rows + 2 * halo_rows`` rows of every field plane
(``planes * itemsize`` bytes a node) and of the aux stack (float32, 4
bytes a node and plane: the flags, and the zonal planes unless the
kernel rebuilds them), and writes ``band_rows`` rows of the field planes
back.  That holds whatever the call's fused depth is.  Not counted: the
settings and zone tables in SMEM and the (8, 128) block of partial sums
of the globals flavor, a few KB a call; so the count is, if anything,
low, and a share reckoned from it is not overstated.

d2q9_kuper at 1024 x 1024 (10 planes f32, 32 bands of 32 rows, halo 8,
one aux plane): (48 x 44 + 32 x 40) x 1024 x 32 = 111,149,056 B a call,
106 B a node; at fuse 4 that is 26.5 B an update against the least
20.5 B.
"""

from __future__ import annotations

AUX_ITEMSIZE = 4      # the aux stack is float32 whatever the storage


def band_read_bytes(row_nodes: int, band_rows: int, halo_rows: int,
                    planes: int, itemsize: int, aux_planes: int) -> int:
    """Bytes one band copies in for one call."""
    per_node = int(planes) * int(itemsize) + AUX_ITEMSIZE * int(aux_planes)
    return (int(band_rows) + 2 * int(halo_rows)) * int(row_nodes) * per_node


def band_write_bytes(row_nodes: int, band_rows: int, planes: int,
                     itemsize: int) -> int:
    """Bytes one band writes back for one call."""
    return int(band_rows) * int(row_nodes) * int(planes) * int(itemsize)


def call_bytes(nodes: int, bands: int, band_rows: int, halo_rows: int,
               pad_rows: int, planes: int, itemsize: int,
               aux_planes: int) -> int:
    """Bytes one kernel call moves over all its bands.  ``nodes`` is the
    physical domain; the bands cover ``bands * band_rows`` rows, of which
    ``pad_rows`` are the engine's ghost rows."""
    rows = int(bands) * int(band_rows) - int(pad_rows)
    if rows < 1 or int(nodes) % rows:
        raise ValueError(f"{nodes} nodes are not {rows} whole rows")
    row_nodes = int(nodes) // rows
    return int(bands) * (
        band_read_bytes(row_nodes, band_rows, halo_rows, planes, itemsize,
                        aux_planes)
        + band_write_bytes(row_nodes, band_rows, planes, itemsize))
