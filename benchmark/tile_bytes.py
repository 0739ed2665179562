"""The bytes a call of the tuned 3D engine's fused kernel really moves
between HBM and VMEM, which is more than ``bytes_model.py``'s least: each
window reads its halo slabs and, where the plane is tiled, its halo rows
again.

The fused kernel (``ops/pallas_d3q.py``) cuts the domain into ``z_bands``
bands of ``band_slabs`` slabs and ``y_bands`` bands of ``band_rows`` rows
(one y band where it holds whole planes), x whole.  For one window, one
call copies in ``band_slabs + 2 * halo_slabs`` slabs of ``band_rows + 2 *
halo_rows`` rows of every field plane (``planes * itemsize`` bytes a
node) and of the aux planes (4 bytes a node and plane: the int32 flags;
the zonal planes are rebuilt in the kernel), and writes the band's own
``band_slabs x band_rows`` rows of the field planes back.  That holds
whatever the call's fused depth is.  Not counted: settings and zone table
in SMEM.  A step that a fused call leaves over goes through a
single-step kernel and is counted at ``bytes_model``'s least, so the
count is, if anything, low, and a share reckoned from it not overstated.

d3q27_cumulant (34 planes f32) at 512 x 48 x 256, bands of 2 slabs, 3
halo slabs, whole planes: 256 x (8 x 140 + 2 x 136) x 12,288 =
4,378,853,376 B a call, 696 B a node; at fuse 3 that is 232 B an update
against the least 91.3 B.
"""

from __future__ import annotations

AUX_ITEMSIZE = 4      # the flag plane rides as int32 whatever the storage


def window_read_bytes(row_nodes: int, band_slabs: int, halo_slabs: int,
                      band_rows: int, halo_rows: int, planes: int,
                      itemsize: int, aux_planes: int) -> int:
    """Bytes one window copies in for one call."""
    per_node = int(planes) * int(itemsize) + AUX_ITEMSIZE * int(aux_planes)
    return ((int(band_slabs) + 2 * int(halo_slabs))
            * (int(band_rows) + 2 * int(halo_rows)) * int(row_nodes)
            * per_node)


def window_write_bytes(row_nodes: int, band_slabs: int, band_rows: int,
                       planes: int, itemsize: int) -> int:
    """Bytes one window writes back for one call."""
    return (int(band_slabs) * int(band_rows) * int(row_nodes)
            * int(planes) * int(itemsize))


def call_bytes(nodes: int, z_bands: int, band_slabs: int, halo_slabs: int,
               y_bands: int, band_rows: int, halo_rows: int, planes: int,
               itemsize: int, aux_planes: int) -> int:
    """Bytes one fused call moves over all its windows."""
    rows = int(z_bands) * int(band_slabs) * int(y_bands) * int(band_rows)
    if rows < 1 or int(nodes) % rows:
        raise ValueError(f"{nodes} nodes are not {rows} whole rows")
    row_nodes = int(nodes) // rows
    return int(z_bands) * int(y_bands) * (
        window_read_bytes(row_nodes, band_slabs, halo_slabs, band_rows,
                          halo_rows, planes, itemsize, aux_planes)
        + window_write_bytes(row_nodes, band_slabs, band_rows, planes,
                             itemsize))
