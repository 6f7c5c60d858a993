#!/usr/bin/env python3
"""How fast sorted rows leave the devices, by the form they leave in.

    python misc/d2h_probe.py [--per-dev 5242880] [--piece 327680]
                             [--live 2500000] [--width 100] [--whole 1]

The mesh sort (``tpumr/parallel/device_sort.py``) ends with each device
holding ``per_dev`` slots of ``width + 1`` uint8 (a row and its validity
byte), the live rows first. This builds such a shard on every device of
the process (content a function of row and column, so every form is
checked byte for byte on the host), and times one PIECE of ``piece`` rows
leaving one device, and one piece from every device at once, in each of
these forms, the slice made on the device by one SPMD program of
``(shards, starts)``:

- ``u8[p,w+1]``   the slots as the sort leaves them
- ``u8[p,w]``     the validity byte dropped
- ``u32[p,w/4]``  the row as little-endian 32-bit words
- ``u32[p*w/4]``  the same words, flat (two ways to make them: strided
  byte columns, or a reshape to ``[p, w/4, 4]``)

and then the whole mechanism for each form: ``live`` rows a device fetched
piece by piece, two rounds in flight, each piece landed in its place in
one ``[live, width]`` array a device. With ``--whole 1`` it first times
``np.asarray`` of the whole sharded array, which is what the mesh sort did
before PR 29. One JSON line per reading on stdout; MB/s counts the bytes of
the ROWS that arrived (``rows x width``), whatever the form carried beside.
"""

import argparse
import json
import statistics
import sys
import time
from functools import partial

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--per-dev", type=int, default=4 * 1310720)
    ap.add_argument("--piece", type=int, default=327680)
    ap.add_argument("--live", type=int, default=2500000)
    ap.add_argument("--width", type=int, default=100)
    ap.add_argument("--whole", type=int, default=1)
    ap.add_argument("--repeats", type=int, default=3)
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    n_dev, per_dev, piece, w = len(devs), a.per_dev, a.piece, a.width
    wp = -(-w // 4) * 4
    mesh = Mesh(np.array(devs), ("data",))

    def say(**kw):
        print(json.dumps(kw), flush=True)

    say(what="device", platform=devs[0].platform, kind=devs[0].device_kind,
        devices=n_dev, per_dev=per_dev, piece=piece, width=w, live=a.live)

    def expect(dev: int, lo: int, rows: int) -> np.ndarray:
        i = np.arange(lo, lo + rows, dtype=np.uint32)[:, None]
        j = np.arange(w, dtype=np.uint32)[None, :]
        return ((i * 7 + j * 13 + (i >> 8) + dev * 31) & 0xFF).astype(
            np.uint8)

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=(), out_specs=P("data"))
    def build():
        dev = lax.axis_index("data").astype(jnp.uint32)
        i = jnp.arange(per_dev, dtype=jnp.uint32)[:, None]
        j = jnp.arange(w + 1, dtype=jnp.uint32)[None, :]
        body = ((i * 7 + j * 13 + (i >> 8) + dev * 31) & 0xFF).astype(
            jnp.uint8)
        return jnp.where(j == w, jnp.uint8(1), body)

    def strided(x):
        b = [x[:, k::4].astype(jnp.uint32) for k in range(4)]
        return b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)

    def reshaped(x):
        b = x.reshape(x.shape[0], wp // 4, 4).astype(jnp.uint32)
        return (b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16)
                | (b[..., 3] << 24))

    def make(form: str):
        @partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                 out_specs=P("data"))
        def _piece(shard, start):
            x = lax.dynamic_slice_in_dim(shard, start[0], piece, axis=0)
            if form == "u8[p,w+1]":
                return x
            x = x[:, :w]
            if form == "u8[p,w]":
                return x
            if wp != w:
                x = jnp.pad(x, ((0, 0), (0, wp - w)))
            y = reshaped(x) if "reshape" in form else strided(x)
            return y.reshape(-1) if form.startswith("u32[p*") else y
        return jax.jit(_piece)

    def rows_of(form: str, arr: np.ndarray) -> np.ndarray:
        """The host's view of one device's piece as [piece, w] uint8."""
        if form == "u8[p,w+1]":
            return arr[:, :w]
        if form == "u8[p,w]":
            return arr
        # a [p, w/4] array may arrive with other strides than numpy's
        # own (the `strides` of each reading): its copy is that form's cost
        return np.ascontiguousarray(arr).view(np.uint8).reshape(
            piece, wp)[:, :w]

    t0 = time.perf_counter()
    shards = jax.block_until_ready(build())
    say(what="build", s=time.perf_counter() - t0,
        bytes=int(shards.size))
    starts_sh = NamedSharding(mesh, P("data"))

    def starts(lo: int):
        return jax.device_put(np.full(n_dev, lo, np.int32), starts_sh)

    if a.whole:
        t0 = time.perf_counter()
        host = np.asarray(shards)
        dt = time.perf_counter() - t0
        say(what="whole np.asarray", s=dt, bytes=int(host.nbytes),
            MBps=host.nbytes / dt / 1e6)
        assert (host[:1000, :w] == expect(0, 0, 1000)).all()
        del host

    forms = ["u8[p,w+1]", "u8[p,w]", "u32[p,w/4]", "u32[p*w/4]",
             "u32[p*w/4] reshape"]
    fns = {form: make(form) for form in forms}      # compiled once each
    for form in forms:
        fn = fns[form]
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(shards, starts(0)))
        compile_s = time.perf_counter() - t0
        for d, s in enumerate(out.addressable_shards):      # byte for byte
            got = rows_of(form, np.asarray(s.data))
            assert got.shape == (piece, w), (form, got.shape)
            assert (got == expect(d, 0, piece)).all(), (form, d)
        for which in ("one", "all"):
            prog, copy = [], []
            for r in range(a.repeats):
                lo = (r + 1) * piece % (per_dev - piece + 1)
                t0 = time.perf_counter()
                out = jax.block_until_ready(fn(shards, starts(lo)))
                t1 = time.perf_counter()
                parts = [s.data for s in out.addressable_shards]
                parts = parts[:1] if which == "one" else parts
                for p in parts:
                    p.copy_to_host_async()
                got = [np.asarray(p) for p in parts]
                t2 = time.perf_counter()
                prog.append(t1 - t0)
                copy.append(t2 - t1)
                assert (rows_of(form, got[-1])[-3:] == expect(
                    len(parts) - 1, lo + piece - 3, 3)).all(), form
            rows_bytes = len(parts) * piece * w
            say(what="piece", form=form, devices=len(parts),
                first_call_s=compile_s, program_s=statistics.median(prog),
                copy_s=statistics.median(copy), copy_s_all=copy,
                carried_bytes=int(sum(g.nbytes for g in got)),
                strides=list(got[0].strides),
                MBps=rows_bytes / statistics.median(copy) / 1e6)

    # the whole mechanism: `live` rows a device, piece by piece
    live = min(a.live, per_dev)
    n_pieces = -(-live // piece)
    for form, depth in [(f, 2) for f in forms] + [(forms[3], n_pieces)]:
        fn = fns[form]
        t0 = time.perf_counter()
        dst = [np.empty((live, w), np.uint8) for _ in range(n_dev)]
        carried = 0
        flight = []

        def launch(k):
            lo = min(k * piece, per_dev - piece)
            out = fn(shards, starts(lo))
            parts = [s.data for s in out.addressable_shards]
            for p in parts:
                p.copy_to_host_async()
            flight.append((k, lo, parts))

        nxt = 0
        while nxt < min(depth, n_pieces):
            launch(nxt)
            nxt += 1
        while flight:
            k, lo, parts = flight.pop(0)
            for d, p in enumerate(parts):
                arr = np.asarray(p)
                carried += arr.nbytes
                take = min(piece, live - k * piece)
                off = k * piece - lo
                dst[d][k * piece:k * piece + take] = \
                    rows_of(form, arr)[off:off + take]
            if nxt < n_pieces:
                launch(nxt)
                nxt += 1
        dt = time.perf_counter() - t0
        for d in range(n_dev):
            assert (dst[d][-5:] == expect(d, live - 5, 5)).all(), form
            assert (dst[d][::9973] == expect(d, 0, live)[::9973]).all(), form
        say(what="fetch live prefix", form=form, in_flight=depth,
            pieces=n_pieces * n_dev, s=dt, carried_bytes=carried,
            rows_bytes=n_dev * live * w, MBps=n_dev * live * w / dt / 1e6)
    return 0


if __name__ == "__main__":
    sys.exit(main())
