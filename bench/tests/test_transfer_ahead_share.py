"""The reader of the ``ahead`` attribute on the program's ``transfer``
spans, on hand-built spans."""

from types import SimpleNamespace as NS

import pytest

from bench.harness import spec

NAME = "engine.transfer_ahead_share"


def _sp(name, track, t0, t1, **attrs):
    return NS(name=name, track=track, t0=t0, t1=t1, attrs=attrs or None)


def _ctx(spans):
    """Window [0, 10] on the host clock."""
    return NS(spans=spans, w0=0.0, w1=10.0, trace=None, offset=None)


def _read(ctx):
    return spec.Bench().metric_reader(NAME)(ctx)


def _spans():
    return [
        _sp("m0", "transfer", 1.0, 1.1, phase="decode", ahead=False),
        _sp("m1", "transfer", 1.2, 1.3, phase="decode", ahead=True),
        _sp("m2", "transfer", 1.4, 1.5, phase="decode", ahead=True),
        _sp("m0", "transfer", 2.0, 2.1, phase="decode", ahead=True),
        _sp("m0", "transfer", 7.0, 7.1, phase="prefill", ahead=False),
        _sp("m1", "transfer", 7.2, 7.3, phase="prefill", ahead=False),
        _sp("m0", "transfer", 10.5, 10.6, phase="decode", ahead=False),
        _sp("m0", "pin", 0.9, 1.0, phase="decode"),
    ]


def test_transfer_ahead_share_reads_decode_transfers_in_the_window():
    """Of the decode-phase transfers that start in the window, the share
    issued ahead; prefill transfers and those past the close do not
    count, and transfers without ``ahead`` (a program that issues every
    DMA on demand and says nothing of it) read nothing."""
    spans = _spans()
    assert _read(_ctx(spans)) == pytest.approx(100.0 * 3 / 4)
    parent = [_sp(s.name, s.track, s.t0, s.t1,
                  **{k: v for k, v in s.attrs.items() if k != "ahead"})
              for s in spans]
    assert _read(_ctx(parent)) is None
