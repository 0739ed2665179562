"""Plain painter for the geometry primitives the benchmark's cases use.

Independent of ``tclb_tpu.utils.geometry``: it reads the generated case
XML and returns boolean masks, which is all the plain references need.
Supported, and nothing else (anything else raises): ``<MRT><Box/></MRT>``,
``<WVelocity><Inlet/></WVelocity>`` (the x = 0 face),
``<EPressure><Outlet/></EPressure>`` (the x = nx-1 face), node types of
the objective group (``<Inlet>``/``<Outlet>`` boxes: they feed globals
only and are ignored), and ``<Wall mask="ALL">`` with ``<Channel/>`` (the
y = 0 and y = ny-1 faces) and ``<Wedge dx nx dy ny direction>``.
Elements paint in document order; a wall clears what lies under it.
"""

from __future__ import annotations

import xml.etree.ElementTree as ET

import numpy as np

IGNORED = ("Inlet", "Outlet")      # objective tags: globals only


def domain_shape(geom: ET.Element) -> tuple[int, ...]:
    """(ny, nx) or (nz, ny, nx) of a <Geometry> element."""
    nx, ny = int(geom.get("nx")), int(geom.get("ny"))
    if geom.get("nz") is None:
        return (ny, nx)
    return (int(geom.get("nz")), ny, nx)


def _wedge(el: ET.Element, ny: int, nx: int) -> np.ndarray:
    dx, wx = int(el.get("dx")), int(el.get("nx"))
    dy, wy = int(el.get("dy")), int(el.get("ny"))
    y, x = np.meshgrid(np.arange(dy, dy + wy), np.arange(dx, dx + wx),
                       indexing="ij")
    xs = (x - dx) / max(wx - 1.0, 1.0)
    ys = (y - dy) / max(wy - 1.0, 1.0)
    direction = el.get("direction", "UpperLeft")
    if direction in ("UpperRight", "LowerRight"):
        xs = 1.0 - xs
    if direction in ("LowerLeft", "LowerRight"):
        ys = 1.0 - ys
    inside = (xs - ys) < 1e-10
    out = np.zeros((ny, nx), bool)
    if dx < 0 or dy < 0 or dx + wx > nx or dy + wy > ny:
        raise ValueError("wedge leaves the domain")
    out[dy:dy + wy, dx:dx + wx] = inside
    return out


def paint(geom: ET.Element) -> dict[str, np.ndarray]:
    """Masks ``collide``, ``wall``, ``inlet`` (WVelocity) and ``outlet``
    (EPressure) of the domain's shape."""
    shape = domain_shape(geom)
    ny, nx = shape[-2], shape[-1]
    m = {k: np.zeros(shape, bool)
         for k in ("collide", "wall", "inlet", "outlet")}
    for el in geom:
        kids = [k.tag for k in el]
        if el.tag == "MRT" and kids == ["Box"] and not el[0].attrib:
            m["collide"][...] = True
        elif el.tag == "WVelocity" and kids == ["Inlet"]:
            m["inlet"][..., :, 0] = True
            m["outlet"][..., :, 0] = False
        elif el.tag == "EPressure" and kids == ["Outlet"]:
            m["outlet"][..., :, nx - 1] = True
            m["inlet"][..., :, nx - 1] = False
        elif el.tag in IGNORED:
            continue
        elif el.tag == "Wall" and el.get("mask") == "ALL":
            for k in el:
                if k.tag == "Channel":
                    w = np.zeros(shape, bool)
                    w[..., 0, :] = True
                    w[..., ny - 1, :] = True
                elif k.tag == "Wedge" and len(shape) == 2:
                    w = _wedge(k, ny, nx)
                else:
                    raise ValueError(f"unsupported wall shape <{k.tag}>")
                m["wall"] |= w
                for other in ("collide", "inlet", "outlet"):
                    m[other] &= ~w
        else:
            raise ValueError(f"unsupported geometry element <{el.tag}>")
    return m


def params(root: ET.Element) -> dict[str, float]:
    """Every attribute of every <Model>/<Params>, as floats."""
    out: dict[str, float] = {}
    for p in root.find("Model").findall("Params"):
        for k, v in p.attrib.items():
            out[k] = float(v)
    return out
