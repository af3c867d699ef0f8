"""Host runtime: the role Kafka Streams' StreamThread plays in the
reference (poll loop, store management, forwarding — KProcessor.java:50-61)
— here: routing wire messages' ids to dense device indices, device
dispatch, byte-exact output-stream reconstruction, and checkpoints."""
