// Native host-side kernels for tclb_tpu.
//
// The reference implements its whole host layer in C++ (geometry/STL
// voxelizer: src/Geometry.cpp.Rt:462-577, VTI output: src/vtkOutput.cpp).
// The TPU compute path here is JAX/XLA/Pallas, but these two host-side
// loops are genuinely hot on large cases — an STL voxelization is
// O(nz*ny*ntri) ray tests and the VTI encoder moves the whole field
// through zlib — so they are native, bound to Python via ctypes
// (tclb_tpu/native/__init__.py) with the pure-Python implementations kept
// as a fallback and as the oracle in tests/test_native.py.
//
// Build: g++ -O3 -std=c++17 -fPIC -shared -pthread tclb_native.cpp -o ... -lz

#include <algorithm>
#include <atomic>
#include <cmath>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

#include <zlib.h>

extern "C" {

// ---------------------------------------------------------------------------
// STL ray-parity voxelizer.
//
// Mirrors tclb_tpu/utils/stl.py::voxelize exactly (same barycentric solve in
// the (y, z) projection, same parity fill, same half-voxel "surface" rule)
// so the two paths are interchangeable; the reference's per-triangle
// scanline rasterizer is src/Geometry.cpp.Rt:462-577.
//
// tri:  (ntri, 3 vertices, 3 coords xyz) C-contiguous doubles
// out:  (nz, ny, nx) bytes, 0/1
// side: 0 = in, 1 = out, 2 = surface
// returns 0 on success
int tclb_voxelize(const double *tri, int64_t ntri,
                  int64_t nx, int64_t ny, int64_t nz,
                  int side, uint8_t *out) {
    if (ntri < 0 || nx <= 0 || ny <= 0 || nz <= 0) return 1;
    std::memset(out, side == 1 ? 1 : 0, (size_t)(nx * ny * nz));

    std::vector<double> zmin(ntri), zmax(ntri), ymin(ntri), ymax(ntri);
    for (int64_t t = 0; t < ntri; t++) {
        const double *p = tri + t * 9;
        zmin[t] = std::min({p[2], p[5], p[8]});
        zmax[t] = std::max({p[2], p[5], p[8]});
        ymin[t] = std::min({p[1], p[4], p[7]});
        ymax[t] = std::max({p[1], p[4], p[7]});
    }

    std::vector<int64_t> sel;
    std::vector<double> xs;
    for (int64_t iz = 0; iz < nz; iz++) {
        const double z = (double)iz;
        sel.clear();
        for (int64_t t = 0; t < ntri; t++)
            if (zmin[t] <= z && zmax[t] >= z) sel.push_back(t);
        if (sel.empty()) continue;
        for (int64_t iy = 0; iy < ny; iy++) {
            const double y = (double)iy;
            xs.clear();
            for (int64_t t : sel) {
                if (ymin[t] > y || ymax[t] < y) continue;
                const double *p = tri + t * 9;
                const double a0 = p[0], a1 = p[1], a2 = p[2];
                const double b0 = p[3], b1 = p[4], b2 = p[5];
                const double c0 = p[6], c1 = p[7], c2 = p[8];
                const double d = (b1 - a1) * (c2 - a2)
                               - (c1 - a1) * (b2 - a2);
                if (std::fabs(d) <= 1e-30) continue;
                const double w1 = ((y - a1) * (c2 - a2)
                                   - (c1 - a1) * (z - a2)) / d;
                const double w2 = ((b1 - a1) * (z - a2)
                                   - (y - a1) * (b2 - a2)) / d;
                if (w1 >= 0.0 && w2 >= 0.0 && w1 + w2 <= 1.0) {
                    const double w0 = 1.0 - w1 - w2;
                    xs.push_back(w0 * a0 + w1 * b0 + w2 * c0);
                }
            }
            if (xs.empty()) continue;
            std::sort(xs.begin(), xs.end());
            uint8_t *row = out + (iz * ny + iy) * nx;
            if (side == 2) {
                // voxel centers within half a cell of a surface crossing;
                // nearbyint rounds half-to-even exactly like Python round()
                for (double xh : xs) {
                    const int64_t i = (int64_t)std::nearbyint(xh);
                    if (i >= 0 && i < nx && std::fabs((double)i - xh) <= 0.5)
                        row[i] = 1;
                }
                continue;
            }
            const uint8_t fill = side == 1 ? 0 : 1;
            for (size_t k = 0; k + 1 < xs.size(); k += 2) {
                int64_t lo = (int64_t)std::ceil(xs[k]);
                int64_t hi = (int64_t)std::floor(xs[k + 1]);
                lo = std::max<int64_t>(lo, 0);
                hi = std::min<int64_t>(hi, nx - 1);
                for (int64_t i = lo; i <= hi; i++) row[i] = fill;
            }
        }
    }
    return 0;
}

// ---------------------------------------------------------------------------
// VTI appended-data zlib block encoder (vtkZLibDataCompressor layout).
//
// VTK's compressed appended block is: a header of UInt32s
// [nblocks, blocksize, last_partial_blocksize, compressed_size_0, ...]
// followed by the concatenated zlib streams of each block.  The reference
// writes raw appended data (src/vtkOutput.cpp); compression is an added
// capability — every VTK reader understands it and large fields shrink ~3x.
//
// The blocks are independent zlib streams, so they are compressed on
// `threads` threads (the calling one among them), each taking the next
// block that nobody has taken yet: blocks cost unequally (the wake against
// the far field), and fixed ranges left the fastest threads waiting.  The
// bytes written do not depend on the thread count.
//
// out must have room for 4*(3+nblocks) + nblocks*compressBound(block).
// Returns total bytes written, or -1 on error.
int64_t tclb_zlib_blocks(const uint8_t *data, int64_t n,
                         int64_t block, int level,
                         uint8_t *out, int64_t outcap, int threads) {
    if (n < 0 || block <= 0) return -1;
    const int64_t nblocks = n == 0 ? 1 : (n + block - 1) / block;
    const int64_t last = n == 0 ? 0 : (n - (nblocks - 1) * block);
    const int64_t header = 4 * (3 + nblocks);
    if (outcap < header) return -1;
    uint32_t *h = (uint32_t *)out;
    h[0] = (uint32_t)nblocks;
    h[1] = (uint32_t)block;
    h[2] = (uint32_t)(last == block ? 0 : last);
    if (threads > nblocks) threads = (int)nblocks;
    if (threads <= 1) {
        int64_t off = header;
        for (int64_t b = 0; b < nblocks; b++) {
            const int64_t sz = b == nblocks - 1 ? last : block;
            uLongf dest = (uLongf)(outcap - off);
            if (compress2(out + off, &dest, data + b * block, (uLong)sz,
                          level) != Z_OK)
                return -1;
            h[3 + b] = (uint32_t)dest;
            off += (int64_t)dest;
        }
        return off;
    }
    // each block goes into its own slot of the worst-case size; one pass
    // afterwards moves the streams together
    const int64_t slot = (int64_t)compressBound((uLong)block);
    if (outcap < header + nblocks * slot) return -1;
    uint8_t *slots = out + header;
    std::atomic<bool> failed{false};
    std::atomic<int64_t> next{0};
    auto work = [&]() {
        for (int64_t b; !failed && (b = next++) < nblocks;) {
            const int64_t sz = b == nblocks - 1 ? last : block;
            uLongf dest = (uLongf)slot;
            if (compress2(slots + b * slot, &dest, data + b * block,
                          (uLong)sz, level) != Z_OK) {
                failed = true;
                return;
            }
            h[3 + b] = (uint32_t)dest;
        }
    };
    std::vector<std::thread> pool;
    try {
        pool.reserve(threads - 1);
        for (int t = 1; t < threads; t++) pool.emplace_back(work);
    } catch (...) {
        // no more threads to be had: those there are take all the blocks
    }
    work();
    for (auto &t : pool) t.join();
    if (failed) return -1;
    int64_t off = header + h[3];
    for (int64_t b = 1; b < nblocks; b++) {
        std::memmove(out + off, slots + b * slot, h[3 + b]);
        off += h[3 + b];
    }
    return off;
}

}  // extern "C"
