"""Plain painter of settings zones in three dimensions, beside
``zones.py`` (which is two-dimensional and stays as it is).  Independent
of ``tclb_tpu.utils.geometry``: it reads the generated case XML and
returns plain arrays indexed ``[z, y, x]``.

Supported, and nothing else (anything else raises): a ``<Geometry nx ny
nz>`` that holds ``<MRT><Box/></MRT>`` (every node collides) and any
number of ``<None name="...">`` elements, each with ``<Sphere dx nx dy ny
dz nz/>`` children.  ``<None>`` changes no node type; its ``name`` opens
a settings zone (numbered in order of first appearance, the default zone
is 0) and the nodes its shapes cover belong to it.  A ``Sphere`` is the
ellipsoid inscribed in its box: node ``(x, y, z)`` is inside when the
sum over the three axes of ``(2 (0.5 + x - dx) / nx - 1)^2`` is below 1
(node centres against the unit sphere).  Elements paint in document
order; a later zone covers an earlier one.  ``zones.zonal`` turns a zonal
setting into one value per node for these arrays too.
"""

from __future__ import annotations

import xml.etree.ElementTree as ET

import numpy as np

from benchmark.reference.zones import DEFAULT_ZONE

AXES = ("z", "y", "x")


def _sphere(el: ET.Element, shape: tuple) -> np.ndarray:
    if set(el.attrib) != {"dx", "nx", "dy", "ny", "dz", "nz"}:
        raise ValueError("<Sphere> takes dx, nx, dy, ny, dz, nz; got "
                         f"{el.attrib}")
    low = [int(el.get("d" + a)) for a in AXES]
    wide = [int(el.get("n" + a)) for a in AXES]
    if any(d < 0 or w < 1 or d + w > n
           for d, w, n in zip(low, wide, shape)):
        raise ValueError("sphere leaves the domain")
    grid = np.meshgrid(*[np.arange(w) for w in wide], indexing="ij")
    r2 = sum((2.0 * (0.5 + g) / w - 1.0) ** 2 for g, w in zip(grid, wide))
    out = np.zeros(shape, bool)
    out[tuple(slice(d, d + w) for d, w in zip(low, wide))] = r2 < 1.0
    return out


def paint(geom: ET.Element) -> dict:
    """``collide``: bool mask of the nodes that collide; ``zone``: the
    settings zone of every node (int); ``names``: zone name -> number."""
    shape = tuple(int(geom.get("n" + a)) for a in AXES)
    collide = np.zeros(shape, bool)
    zone = np.full(shape, DEFAULT_ZONE, np.int32)
    names = {"DefaultZone": DEFAULT_ZONE}
    for el in geom:
        kids = [k.tag for k in el]
        if el.tag == "MRT" and kids == ["Box"] and not el[0].attrib \
                and not el.attrib:
            collide[...] = True
        elif el.tag == "None" and set(el.attrib) == {"name"} and kids \
                and set(kids) == {"Sphere"}:
            number = names.setdefault(el.get("name"), len(names))
            for k in el:
                zone[_sphere(k, shape)] = number
        else:
            raise ValueError(f"unsupported geometry element <{el.tag}>")
    return {"collide": collide, "zone": zone, "names": names}
