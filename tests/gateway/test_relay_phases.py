"""The stream relay, timed (gateway/api_openai._forward_stream, both pumps;
metrics.RelayStats): the three phases sum to the pump's wall time, chunks and
bytes are counted as they pass, the forwarded bytes are what the upstream
sent, and the gateway's /metrics serves the relay and its own CPU seconds."""

import asyncio
import itertools
import re

import aiohttp
import pytest

from llmlb_tpu.gateway import api_openai
from llmlb_tpu.gateway.types import EndpointType
from tests.support import (
    GatewayHarness,
    MockOpenAIEndpoint,
    MockResumableEndpoint,
    assert_sse_protocol,
)

CHAT = "/v1/chat/completions"
BODY = {"model": "m", "stream": True,
        "messages": [{"role": "user", "content": "ping"}]}
PHASES = ("upstream_wait", "feed", "client_write")


def _relay(gw) -> dict:
    r = gw.state.metrics.relay
    return {k: getattr(r, k) for k in ("chunks", "bytes") + PHASES}


async def _one_stream(gw) -> bytes:
    r = await gw.client.post(CHAT, json=BODY,
                             headers=await gw.inference_headers())
    assert r.status == 200, await r.text()
    return await r.read()


@pytest.mark.parametrize("armed", [False, True],
                         ids=["plain-pump", "armed-pump"])
def test_the_phases_sum_to_the_pumps_wall_time(monkeypatch, armed):
    """With a clock that advances by one at every read, a pump whose every
    stretch between two reads is booked under a phase books exactly
    (reads - 1) seconds: no stretch is lost, none is booked twice."""
    async def run():
        gw = await GatewayHarness.create()
        if armed:
            mock = await MockResumableEndpoint(
                model="m", script=list(range(100, 112))).start()
            gw.register_mock(mock.url, ["m"], name="eng",
                             endpoint_type=EndpointType.TPU)
        else:
            mock = await MockOpenAIEndpoint(
                model="m", tokens_per_reply=9,
                inter_chunk_delay_s=0.002).start()
            gw.register_mock(mock.url, ["m"], name="eng")
        try:
            reads = itertools.count()
            monkeypatch.setattr(api_openai, "_now",
                                lambda: float(next(reads)))
            before = _relay(gw)
            body = await _one_stream(gw)
            n_reads = next(reads)
            after = _relay(gw)
            d = {k: after[k] - before[k] for k in after}
            assert n_reads > 3 and d["chunks"] >= 1
            assert sum(d[p] for p in PHASES) == n_reads - 1
            assert all(d[p] >= 0 for p in PHASES)
            if not armed:  # one read after each feed, one after each write
                assert d["feed"] == d["client_write"] == d["chunks"]
            # every byte the client got was counted, and nothing else
            assert d["bytes"] == len(body)
            assert_sse_protocol(body, "openai")
            return body, mock
        finally:
            await mock.stop()
            await gw.close()

    body, _mock = asyncio.run(run())
    assert body.rstrip().endswith(b"data: [DONE]")
    # the armed pump strips the gateway's own replay frames and no other
    assert b"llmlb.replay" not in body


def test_the_plain_pump_forwards_the_upstreams_bytes_unchanged():
    async def run():
        gw = await GatewayHarness.create()
        mock = await MockOpenAIEndpoint(model="m", tokens_per_reply=17).start()
        gw.register_mock(mock.url, ["m"], name="eng")
        try:
            through = await _one_stream(gw)
            async with aiohttp.ClientSession() as session:
                async with session.post(mock.url + CHAT, json=BODY) as r:
                    direct = await r.read()
            assert through == direct
            relay = _relay(gw)
            assert relay["bytes"] == len(direct) and relay["chunks"] >= 1
            # real clock: the phases are the stream's wall time, about
            assert all(relay[p] >= 0 for p in PHASES)
            # served on the gateway's /metrics route, with the process's
            # own CPU seconds
            text = await (await gw.client.get("/metrics")).text()
            assert f"llmlb_gateway_relay_chunks_total {relay['chunks']}" in text
            assert f"llmlb_gateway_relay_bytes_total {relay['bytes']}" in text
            for phase in PHASES:
                assert ('llmlb_gateway_relay_seconds_total{phase="%s"}'
                        % phase) in text
            cpu = dict(re.findall(
                r'llmlb_gateway_cpu_seconds_total\{class="(\w+)"\} (\S+)',
                text))
            assert set(cpu) == {"process", "loop", "other"}
            assert 0 < float(cpu["loop"]) <= float(cpu["process"]) + 1e-3
            assert float(cpu["other"]) >= 0
        finally:
            await mock.stop()
            await gw.close()

    asyncio.run(run())
