"""The metric-aggregator deployment's actor (rio-rs
``examples/metric-aggregator/src/services.rs:30-49``): one
``MetricAggregator`` per metric name keeps running stats in managed state,
saves them, and fans each sample out to the aggregator of ``name.tag``,
which saves too, before the acknowledgement.

The directory seats a name and its tags on any of the 8 nodes, so the
fan-out goes through a real ``Client`` (``ServiceObject.send`` is for a
target on the same node and answers a remote one with a redirect)."""

from rio_tpu import AppData, Client, Registry, ServiceObject, handler, message
from rio_tpu.registry.identifiable import type_id
from rio_tpu.state import managed_state


@message
class Metric:
    tag: str = ""
    value: float = 0.0


@message
class Stats:
    count: int = 0
    total: float = 0.0
    vmin: float = 0.0
    vmax: float = 0.0


class MetricAggregator(ServiceObject):
    stats = managed_state(Stats)

    @handler
    async def record(self, msg: Metric, ctx: AppData) -> Stats:
        s = self.stats
        s.vmin = msg.value if s.count == 0 else min(s.vmin, msg.value)
        s.vmax = msg.value if s.count == 0 else max(s.vmax, msg.value)
        s.count += 1
        s.total += msg.value
        await self.save_state(ctx)
        if msg.tag:
            await ctx.get(Client).send(
                MetricAggregator, f"{self.id}.{msg.tag}",
                Metric(tag="", value=msg.value), returns=Stats,
            )
        return s


TYPE = type_id(MetricAggregator)
HANDLER = (TYPE, type_id(Metric))
STATE_TYPE = type_id(Stats)
FANOUT_CLIENT = True  # the servers' AppData holds a Client for the fan-out


def registry() -> Registry:
    return Registry().add_type(MetricAggregator)


def object_names(config: dict) -> list[str]:
    names = [f"m{i}" for i in range(config["metric_names"])]
    tags = [f"tag{t}" for t in range(config["tags"])]
    return names + [f"{n}.{t}" for n in names for t in tags]


async def send_metric(client, name: str, tag: str, value: float) -> None:
    await client.send(MetricAggregator, name, Metric(tag=tag, value=value), returns=Stats)
