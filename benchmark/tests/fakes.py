"""Stand-ins for the device and for the system under test: the harness's
own flow can then be driven in milliseconds, and broken on purpose."""

import json
import time

NATIVE_COUNTERS = {
    "quotient.resident_coset_sweeps": 8, "quotient.coset_sweeps": 8,
    "fri.resident_folds": 12, "fri.folds": 12,
    "ntt.resident_transforms": 2, "merkle.resident_commits": 2,
    "deep.resident_codewords": 1, "host.blocking_syncs": 8,
}


class FakeDevice:
    def __init__(self, platform="tpu", kind="TPU v5 lite", peak=5 * 2**30):
        self.platform, self.device_kind, self._peak = platform, kind, peak

    def memory_stats(self):
        return {"peak_bytes_in_use": self._peak}


class FakeSystem:
    """A 'prover' whose proof is a small JSON object that 'verifies' when its
    opened value is the seed's."""

    def __init__(self, tmp, devices=None, counters=None):
        self.cache_dir = str(tmp)
        self.devices = devices if devices is not None else [FakeDevice()]
        self.counters = dict(NATIVE_COUNTERS if counters is None else counters)
        self.proves = 0
        self.warmed = []  # skip_setup of each warm_library call
        self.key = "k0"

    def start(self):
        return self.devices

    def drain(self):
        pass

    def synthesize(self, cell, seed):
        self.cell, self.seed = cell, seed
        return 1024

    def setup_key(self, cell, trace_len):
        return self.key

    def warm_library(self, workers, skip_setup=False):
        self.warmed.append(skip_setup)
        return []

    def generate_setup(self):
        pass

    def value(self):
        return (1 << 40) + self.seed

    def prove(self):
        self.proves += 1
        time.sleep(0.002)
        return {"values_at_z": [[self.value(), 1]], "queries": [1, 2, 3]}

    def proof_bytes(self, proof):
        return json.dumps(proof).encode()

    def proof_from_bytes(self, blob):
        return json.loads(blob)

    def verify(self, proof):
        return proof["values_at_z"][0][0] == self.value()

    def recorded_prove(self):
        return self.prove(), dict(self.counters)

    def annotate_spans(self, trace_dir):
        pass

    def damage(self, blob):
        from benchmark.system import BoojumSystem

        return BoojumSystem.damage(self, blob)

    def peak_bytes(self, devices):
        return max(d.memory_stats()["peak_bytes_in_use"] for d in devices)

    def save_setup(self, path):
        self.saved = getattr(self, "saved", 0) + 1
        with open(path, "w") as f:
            f.write("setup")

    def load_setup(self, path):
        self.loaded = getattr(self, "loaded", 0) + 1
