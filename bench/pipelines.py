"""The system under test, built from a configuration file.

The stateful prefix comes from the program's own
``traffic.flow_feature_stages`` (FlowKey on ``flow_id``, 28-word registers,
WindowStats); the suffix kind's module builds the classifier stages from
weights the benchmark makes; a ``mitigation`` entry appends ``Mitigate``.
The engine is ``PacketServeEngine`` on one chip and
``ShardedPacketServeEngine`` on several, over
``StatefulPipeline(stages, backend="pallas")``.
"""

from __future__ import annotations

from bench import reference, spec

FEATURE_DIM = 4


class Built:
    """The configuration's program and what the reference needs."""

    def __init__(self, config: dict):
        from repro.core import stageir
        from repro.data import traffic
        from repro.flowstate import MitigationSpec, StatefulPipeline

        self.config = config
        pre = config["prefix"]
        n_slots = int(config["n_slots"])
        (fk, ru, ws), _ = traffic.flow_feature_stages(
            n_slots=n_slots, pl_bins=int(pre["pl_bins"]),
            ipt_bins=int(pre["ipt_bins"]), ewma_alpha=float(pre["ewma_alpha"]))
        self.suffix = spec.suffix_kind(config["suffix"]["kind"])
        self.params = self.suffix.params(config["suffix"])
        stages = [fk, ru, ws] + self.suffix.stages(self.params)
        mit = config.get("mitigation")
        if mit:
            stages.append(stageir.Mitigate(MitigationSpec(
                n_slots=int(mit["n_slots"]), mode=mit.get("mode", "drop"),
                threshold=int(mit["threshold"]),
                keep_every=int(mit.get("keep_every", 8)),
                attack_class=int(mit.get("attack_class", 1)))))
        self.stages = stages
        self.pipeline = StatefulPipeline(stages, backend="pallas")
        self.registers = reference.flow_registers(pre)
        if self.registers.width != ru.spec.width:
            raise ValueError("reference register layout differs from the "
                             f"program's ({ru.spec.width} words)")

    @property
    def mitigation(self) -> dict | None:
        return self.config.get("mitigation")

    def engine(self, n_devices: int):
        eng = self.config["engine"]
        if n_devices > 1:
            import jax

            from repro.serve import ShardedPacketServeEngine

            return ShardedPacketServeEngine(
                self.pipeline, feature_dim=FEATURE_DIM,
                max_batch=int(eng["max_batch"]), depth=int(eng["depth"]),
                devices=jax.devices()[:n_devices])
        from repro.serve.packet_engine import PacketServeEngine

        return PacketServeEngine(self.pipeline, feature_dim=FEATURE_DIM,
                                 max_batch=int(eng["max_batch"]),
                                 depth=int(eng["depth"]))
