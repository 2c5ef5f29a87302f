"""The presence deployment's actor: one ``Presence`` per user, heartbeats,
volatile state (rio-rs ``examples/presence/src/services.rs:25-55``). The
acknowledgement names the server that handled the beat, which is what the
audits compare with the directory."""

from rio_tpu import AppData, Registry, ServerInfo, ServiceObject, handler, message
from rio_tpu.registry.identifiable import type_id


@message
class Beat:
    n: int = 1


@message
class BeatAck:
    n: int = 0
    server: str = ""


class Presence(ServiceObject):
    def __init__(self):
        self.beats = 0

    @handler
    async def beat(self, msg: Beat, ctx: AppData) -> BeatAck:
        self.beats += msg.n
        return BeatAck(n=self.beats, server=ctx.get(ServerInfo).address)


TYPE = type_id(Presence)
HANDLER = (TYPE, type_id(Beat))  # the RED histogram's key


def registry() -> Registry:
    return Registry().add_type(Presence)


def object_names(config: dict) -> list[str]:
    return [str(i) for i in range(config["objects"])]


async def heartbeat(client, user: str) -> str:
    """One request; returns the address of the server that answered."""
    ack = await client.send(Presence, user, Beat(), returns=BeatAck)
    return ack.server
