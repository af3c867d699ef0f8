"""Transport bridge: the L0/L4 edge of the framework.

The reference's transport is an external Kafka broker with two topics
(`MatchIn`, `MatchOut`, one partition each — /root/reference/topic.js:14-25)
between the Node harness and the Streams engine. Here the same contract
is a small native-Python stack:

- broker.py   — the broker core: named topics, single-partition ordered
                logs, offset-based fetch (the semantics the reference
                relies on: 1 partition => total order).
- tcp.py      — the process boundary: a JSON-lines TCP server/client pair
                exposing the broker API on a socket, so the provisioner,
                load generator, engine service and consumer run as
                separate OS processes like the reference's stack.
- service.py  — the engine service: polls MatchIn, runs a configurable
                engine (the device seq engine, the native engine or
                the scalar oracle replica),
                forwards the IN/OUT record stream to MatchOut
                (KProcessor.java:97, 124).
- provision.py/serve.py/consume.py — the CLI roles (topic.js /
                KProcessor.main / consumer.js).
"""

from kme_tpu.bridge.broker import BrokerError, InProcessBroker, Record

__all__ = ["BrokerError", "InProcessBroker", "Record"]
