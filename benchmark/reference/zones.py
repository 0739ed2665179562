"""Plain painter for what ``reference/geometry.py`` refuses: settings
zones.  Independent of ``tclb_tpu.utils.geometry``: it reads the
generated case XML and returns plain arrays.

Supported, and nothing else (anything else raises): a two-dimensional
``<Geometry nx ny>`` that holds ``<MRT><Box/></MRT>`` (every node
collides) and any number of ``<None name="...">`` elements, each with
``<Sphere dx nx dy ny/>`` children.  ``<None>`` changes no node type; its
``name`` opens a settings zone (numbered in order of first appearance,
the default zone is 0) and the nodes its shapes cover belong to it.  A
``Sphere`` is the ellipse inscribed in its box: node ``(x, y)`` is inside
when ``((2 (x - dx) + 1) / nx - 1)^2 + ((2 (y - dy) + 1) / ny - 1)^2 < 1``
(node centres against the unit circle).  Elements paint in document
order; a later zone covers an earlier one.

A zonal setting is written ``<Params Name="v" Name-zone="w"/>``:
:func:`zonal` turns it into one value per node.
"""

from __future__ import annotations

import xml.etree.ElementTree as ET

import numpy as np

DEFAULT_ZONE = 0


def _sphere(el: ET.Element, ny: int, nx: int) -> np.ndarray:
    if set(el.attrib) != {"dx", "nx", "dy", "ny"}:
        raise ValueError(f"<Sphere> takes dx, nx, dy, ny; got {el.attrib}")
    dx, wx = int(el.get("dx")), int(el.get("nx"))
    dy, wy = int(el.get("dy")), int(el.get("ny"))
    if dx < 0 or dy < 0 or wx < 1 or wy < 1 or dx + wx > nx or dy + wy > ny:
        raise ValueError("sphere leaves the domain")
    y, x = np.meshgrid(np.arange(dy, dy + wy), np.arange(dx, dx + wx),
                       indexing="ij")
    xs = (2.0 * (x - dx) + 1.0) / wx - 1.0
    ys = (2.0 * (y - dy) + 1.0) / wy - 1.0
    out = np.zeros((ny, nx), bool)
    out[dy:dy + wy, dx:dx + wx] = xs * xs + ys * ys < 1.0
    return out


def paint(geom: ET.Element) -> dict:
    """``collide``: bool mask of the nodes that collide; ``zone``: the
    settings zone of every node (int); ``names``: zone name -> number."""
    if geom.get("nz") is not None:
        raise ValueError("zones are painted in two dimensions only")
    ny, nx = int(geom.get("ny")), int(geom.get("nx"))
    collide = np.zeros((ny, nx), bool)
    zone = np.full((ny, nx), DEFAULT_ZONE, np.int32)
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
                zone[_sphere(k, ny, nx)] = number
        else:
            raise ValueError(f"unsupported geometry element <{el.tag}>")
    return {"collide": collide, "zone": zone, "names": names}


def zonal(params: dict, painted: dict, name: str, default: float
          ) -> np.ndarray:
    """The setting ``name`` at every node (float64): ``params[name]``
    (or ``default``), and ``params[name-zone]`` where the node lies in
    ``zone``.  A zone that the geometry does not name raises."""
    base = float(params.get(name, default))
    out = np.full(painted["zone"].shape, base, np.float64)
    for key, value in params.items():
        if not key.startswith(name + "-"):
            continue
        zone_name = key[len(name) + 1:]
        if zone_name not in painted["names"]:
            raise ValueError(f"{key}: the geometry has no zone "
                             f"{zone_name!r}")
        out[painted["zone"] == painted["names"][zone_name]] = float(value)
    return out
