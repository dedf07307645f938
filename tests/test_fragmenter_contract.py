"""What any fragmenter owes its caller, held once over the registry
(``config.FRAGMENTER_KINDS``, ``fragmenter/base.py`` ``get_fragmenter``);
and what became of the kinds retired at PR 46 (``cdc-tpu``,
``cdc-aligned``, ``cdc-aligned-tpu``): refused by name, with the kinds
that remain."""

import hashlib
import logging

import numpy as np
import pytest

from dfs_tpu.cli.main import build_parser
from dfs_tpu.cli.main import main as cli_main
from dfs_tpu.config import FRAGMENTER_KINDS, CDCParams, FragmenterConfig
from dfs_tpu.fragmenter.base import get_fragmenter
from tests.test_cdc_anchored import SMALL as ANCHORED

RETIRED = ("cdc-tpu", "cdc-aligned", "cdc-aligned-tpu")

# toy geometries (the device chain compiles in seconds at these): 4 KiB
# lanes for the anchored kinds, 1 KiB chunks for the Gear one
GEAR = CDCParams(min_size=64, avg_size=256, max_size=1024)
PARTS = 5
# FRAGMENTER_KINDS and the one thing --cdc-devices means: the anchored
# walk over (here two virtual) devices, 16 KiB windows
ROWS = (*FRAGMENTER_KINDS, "cdc-anchored@2")


def _build(kind: str):
    """-> (fragmenter, the kind it answers to, the least and the most
    bytes a chunk of an n-byte input may have)."""
    if kind == "fixed":         # positional: always PARTS chunks
        return (get_fragmenter(kind, fixed_parts=PARTS), kind,
                lambda n: (n // PARTS, -(-n // PARTS)))
    if kind == "cdc":
        return (get_fragmenter(kind, cdc_params=GEAR), kind,
                lambda n: (1, GEAR.max_size))
    # the CPU on purpose (conftest): auto resolves to the CPU engine
    resolved = "cdc-anchored" if kind == "auto" else kind
    frag = None
    if kind == "cdc-anchored@2":
        resolved = kind = "cdc-anchored"
        frag = FragmenterConfig(devices=2, region_bytes=4 * 4096)
    return (get_fragmenter(kind, cdc_params=ANCHORED, frag=frag), resolved,
            lambda n: (1, ANCHORED.chunk.max_blocks * 64))


@pytest.mark.parametrize("n", [0, 1, 300001])
@pytest.mark.parametrize("kind", ROWS)
def test_every_kind_keeps_the_plugin_contract(kind, n):
    """``chunk``, ``manifest``, ``manifest_stream`` and ``chunks_stream``
    agree with each other; the chunks tile the input in order under the
    kind's bound and are named by their sha256; the stream's ``store``
    sees every payload; the manifest says which kind cut it."""
    frag, name, bounds = _build(kind)
    least, most = bounds(n)
    assert frag.name == name
    data = np.random.default_rng(n).integers(
        0, 256, size=n, dtype=np.uint8).tobytes()
    blocks = [data[i:i + 70001] for i in range(0, n, 70001)]

    table = [(c.offset, c.length, c.digest) for c in frag.chunk(data)]
    end = 0
    for o, ln, dg in table:
        assert o == end and least <= ln <= most
        assert dg == hashlib.sha256(data[o:o + ln]).hexdigest()
        end = o + ln
    assert end == n

    m = frag.manifest(data, name="f")
    stored: dict[str, bytes] = {}
    ms = frag.manifest_stream(iter(blocks), name="f",
                              store=lambda d, b: stored.setdefault(
                                  d, bytes(b)))
    streamed = [c for batch in frag.chunks_stream(iter(blocks))
                for c in batch]
    for got in (m.chunks, ms.chunks, streamed):
        assert [(c.offset, c.length, c.digest) for c in got] == table
        assert [c.index for c in got] == list(range(len(table)))
    assert m.fragmenter == ms.fragmenter == name
    assert (m.size, ms.size) == (n, n) and m.file_id == ms.file_id
    assert b"".join(stored[dg] for _, _, dg in table) == data
    assert not getattr(frag, "_unavailable", False)    # no walk degraded


@pytest.mark.parametrize("command", ["serve", "sidecar"])
def test_the_flag_offers_the_registry_s_kinds_and_no_other(command):
    sub = next(a for a in build_parser()._actions
               if isinstance(a.choices, dict) and command in a.choices)
    flag = next(a for a in sub.choices[command]._actions
                if "--fragmenter" in a.option_strings)
    assert tuple(flag.choices) == FRAGMENTER_KINDS == (
        "auto", "fixed", "cdc", "cdc-anchored", "cdc-anchored-tpu")
    assert flag.default == "auto"


# ------------------------------------------------------------ retired --

def _via_factory(kind, capsys):
    with pytest.raises(ValueError) as e:
        get_fragmenter(kind)
    return str(e.value)


def _via_cli(*argv):
    def run(kind, capsys):
        with pytest.raises(SystemExit) as e:
            cli_main([*argv, "--fragmenter", kind])
        assert e.value.code == 2
        return capsys.readouterr().err
    return run


@pytest.mark.parametrize("kind", RETIRED)
@pytest.mark.parametrize("ask", [
    _via_factory,
    _via_cli("serve", "--node-id", "1", "--nodes", "1"),
    _via_cli("sidecar"),
], ids=["get_fragmenter", "serve", "sidecar"])
def test_a_retired_kind_is_refused_by_name(ask, kind, capsys):
    said = ask(kind, capsys)
    assert kind in said
    assert all(k in said for k in FRAGMENTER_KINDS), said
    rest = said.replace(kind, "")
    assert not any(r in rest for r in RETIRED), said


def test_cdc_devices_with_the_gear_kind_is_said_not_silent(caplog):
    """``--cdc-devices`` means the anchored walk over that many devices;
    asked of ``cdc`` (which sharded a walk of its own until PR 46) it is
    warned about at start-up, as ``cdc-anchored-tpu`` always was — the
    node's ``/metrics`` ``frag.devices`` would read 2 either way."""
    from dfs_tpu.fragmenter.cdc_cpu import CpuCdcFragmenter

    # the handler on the logger itself: a node started earlier in this
    # process leaves "dfs_tpu" with propagate off (utils/logging.py)
    log = logging.getLogger("dfs_tpu.fragmenter")
    log.addHandler(caplog.handler)
    try:
        frag = get_fragmenter("cdc", cdc_params=GEAR,
                              frag=FragmenterConfig(devices=2))
    finally:
        log.removeHandler(caplog.handler)
    assert type(frag) is CpuCdcFragmenter
    warned = [r.getMessage() for r in caplog.records]
    assert any("--cdc-devices is ignored" in w and "'cdc'" in w
               and "cdc-anchored" in w for w in warned), warned
