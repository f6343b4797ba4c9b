"""Reconfiguration tests: compile cache (warm PR), CRC tamper detection,
topology/binding legality — the paper's cross-PRR reprogram attack."""
import numpy as np
import pytest

from repro.core.isolation import IsolationAuditor
from repro.core.reconfig import (Bitfile, CompileService, LegalityError,
                                 ProgramLoader, ProgramRequest)
from repro.core.vslice import SliceSpec, VSlice


class FakeDev:
    def __init__(self, i):
        self.id = i


def mkslice(sid, origin=(0, 0), shape=(1, 1), base=0):
    n = shape[0] * shape[1]
    devs = np.array([FakeDev(base + i) for i in range(n)]).reshape(shape)
    vs = VSlice.__new__(VSlice)
    vs.slice_id = sid
    vs.spec = SliceSpec(origin, shape)
    vs.devices = devs
    vs.axis_names = ("data", "model")
    vs.healthy = True
    vs.mesh = None        # fake-builder tests never lower against it
    return vs


def mkbitfile(vs, key="prog"):
    return Bitfile(key, vs.topology_key, vs.fingerprint,
                   compiled=lambda *a: "ran", abstract_args=())


def quiesce_noop():
    from contextlib import contextmanager

    @contextmanager
    def q():
        yield
    return q


def test_load_and_run():
    vs = mkslice(0)
    loader = ProgramLoader()
    prog = loader.load(mkbitfile(vs), vs, quiesce_noop())
    assert prog() == "ran"
    assert loader.reconfigs == 1


def test_crc_tamper_detected():
    vs = mkslice(0)
    bf = mkbitfile(vs)
    bf.crc = "deadbeef00000000"            # bit-rot / tampering
    loader = ProgramLoader(auditor=IsolationAuditor())
    with pytest.raises(LegalityError, match="CRC"):
        loader.load(bf, vs, quiesce_noop())
    assert loader.auditor.count("bitfile_crc_fail") == 1


def test_topology_mismatch_rejected():
    vs1 = mkslice(0, shape=(1, 1))
    vs2 = mkslice(1, shape=(1, 2), base=10)
    bf = mkbitfile(vs1)
    loader = ProgramLoader(auditor=IsolationAuditor())
    with pytest.raises(LegalityError, match="topology"):
        loader.load(bf, vs2, quiesce_noop())


def test_cross_slice_reprogram_attack_rejected():
    """The paper's §IV.C scenario: VM0's bitfile flashed at VM1's PRR of
    the SAME topology must be rejected on slice binding."""
    vs0 = mkslice(0, origin=(0, 0), base=0)
    vs1 = mkslice(1, origin=(0, 1), base=100)
    assert vs0.topology_key == vs1.topology_key
    bf0 = mkbitfile(vs0)
    loader = ProgramLoader(auditor=IsolationAuditor())
    with pytest.raises(LegalityError, match="bound to a different slice"):
        loader.load(bf0, vs1, quiesce_noop(), owner="vm0")
    assert loader.auditor.count("cross_slice_reprogram") == 1


def test_compile_cache_warm_rebind():
    """Same program re-flashed on the same slice → warm hit
    (compile_seconds == 0). A slice of the same topology class on other
    devices compiles its own executable, bound to its own devices: the
    first slice's executable would run on the first slice's chips."""
    svc = CompileService(step_builder=_fake_builder)
    req = ProgramRequest("qwen1.5-0.5b", "decode", 32, 2)
    vs0 = mkslice(0, base=0)
    vs1 = mkslice(1, base=50)
    bf0 = svc.compile(req, vs0)
    assert svc.misses == 1 and bf0.compile_seconds > 0
    warm = svc.compile(req, vs0)
    assert svc.hits == 1 and warm.compile_seconds == 0.0
    assert warm.compiled is bf0.compiled
    bf1 = svc.compile(req, vs1)
    assert svc.misses == 2 and bf1.compiled is not bf0.compiled
    assert bf1.slice_fingerprint == vs1.fingerprint
    loader = ProgramLoader()
    loader.load(bf1, vs1, quiesce_noop())


def _fake_builder(cfg, mesh, cell):
    class J:
        def lower(self, *a):
            return self

        def compile(self):
            return lambda *a: "ran"
    return J(), ()
