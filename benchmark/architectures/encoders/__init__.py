"""Encoder towers served beside a decoder (a configuration's `encoders`),
one module per architecture, found by `architectures.load_encoder`."""
