"""NEXmark Bid rows as JSON, from a seed: the Bid record of the Flink suite
(github.com/nexmark/nexmark) at its seven columns, with the key model of its
`BidGenerator` / `GeneratorConfig`, written down from memory of that source
(the sealed machines have no network; the configuration's `assumed` says which
parts are the source's and which were set here).

The generator's event stream is person : auction : bid = 1 : 3 : 46 in every
50 events. Only the bids are made into rows, but persons and auctions advance
the id counters as in the source: bid i of the pool is event
`(i // 46) * 50 + 4 + i % 46`, and `event // 50 + 1` persons exist by then.

  bidder   with chance 1 - 1/hot_bidders_ratio the hot bidder
           `(last_person // 100) * 100 + 1` (moves on every 100 new persons
           = 4,600 bids); else uniform over the last `num_active_people`
           persons and a lead of 10 ids not yet created; + 1,000
  auction  with chance 1 - 1/hot_auction_ratio `(last_auction // 100) * 100`;
           else uniform over the last 100 auctions and a lead of 10; + 1,000
  price    round(10 ** (6 u) * 100), u uniform in [0, 1)
  channel  one bid in two from Google / Facebook / Baidu / Apple with a fixed
           url each; else `channel-<n>`, n uniform in 0..9999, whose url is
           `https://www.nexmark.com/<w>/<w>/<w>/item.htm?query=1&channel_id=<n>`
           (three words per channel, fixed for the run, as the source caches)
  dateTime epoch milliseconds of the event at `events_per_s`
  extra    lower-case filler of 54..81 characters: the source pads a bid to
           an average of 100 bytes, of which its four longs are 32

Parameters (the configuration's `rows`; a traffic mix's `keys` entry means
nothing here and is ignored): pool_rows, drain_rows, key_column, and `bids`
with the model's numbers. `Pool.keys` is each row's bidder as an index into
`Pool.ids` (the sorted distinct bidder ids), `Pool.values` its price.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

HOT_CHANNELS = (b"Google", b"Facebook", b"Baidu", b"Apple")
ROW = (b'{"auction":%d,"bidder":%d,"price":%d,"channel":"%s","url":"%s",'
       b'"dateTime":%d,"extra":"%s"}')


@dataclass
class Pool:
    drains: list  # drains[i] is a list of `drain_rows` bytes payloads
    keys: np.ndarray  # int64 [n_drains, drain_rows], index into `ids`
    values: np.ndarray  # int64 [n_drains, drain_rows], the bid's price
    drain_rows: int
    n_keys: int
    ids: np.ndarray  # int64 [n_keys], the distinct bidder ids, sorted


def _words(rng, n: int, length: int = 5) -> list:
    letters = rng.integers(97, 123, (n, length), dtype=np.uint8)
    return [bytes(row) for row in letters]


def bidders_and_auctions(rng, n: int, m: dict):
    """(bidder ids, auction ids, event numbers) of the first `n` bids."""
    pp, ap, bp = (int(m["person_proportion"]), int(m["auction_proportion"]),
                  int(m["bid_proportion"]))
    total = pp + ap + bp
    i = np.arange(n, dtype=np.int64)
    event = (i // bp) * total + pp + ap + i % bp
    epoch = event // total
    last_person = epoch * pp + pp - 1
    people = last_person + 1
    active = np.minimum(people, int(m["num_active_people"]))
    hot = rng.integers(0, int(m["hot_bidders_ratio"]), n) > 0
    stride = int(m["hot_bidder_stride"])
    cold = people - active + np.floor(
        rng.random(n) * (active + int(m["person_id_lead"]))).astype(np.int64)
    bidder = np.where(hot, (last_person // stride) * stride + 1, cold) \
        + int(m["first_person_id"])
    last_auction = epoch * ap + ap - 1
    low = np.maximum(last_auction - int(m["num_in_flight_auctions"]), 0)
    hot = rng.integers(0, int(m["hot_auction_ratio"]), n) > 0
    stride = int(m["hot_auction_stride"])
    cold = low + np.floor(rng.random(n) * (
        last_auction - low + 1 + int(m["auction_id_lead"]))).astype(np.int64)
    auction = np.where(hot, (last_auction // stride) * stride, cold) \
        + int(m["first_auction_id"])
    return bidder, auction, event


def make(seed: int, params: dict) -> Pool:
    rng = np.random.default_rng(seed)
    m = params["bids"]
    n, drain_rows = int(params["pool_rows"]), int(params["drain_rows"])
    if n % drain_rows:
        raise ValueError("the pool holds whole drains")
    bidder, auction, event = bidders_and_auctions(rng, n, m)
    price = np.rint(10.0 ** (rng.random(n) * 6.0) * 100.0).astype(np.int64)
    n_channels = int(m["channels_number"])
    words = _words(rng, 3 * (n_channels + len(HOT_CHANNELS)))
    base = [b"https://www.nexmark.com/%s/%s/%s/item.htm?query=1"
            % tuple(words[3 * c:3 * c + 3])
            for c in range(n_channels + len(HOT_CHANNELS))]
    # channel table: the numbered channels, then the four hot ones
    channels = [b"channel-%d" % c for c in range(n_channels)] \
        + list(HOT_CHANNELS)
    urls = [base[c] + b"&channel_id=%d" % c for c in range(n_channels)] \
        + base[n_channels:]
    hot = rng.integers(0, int(m["hot_channels_ratio"]), n) > 0
    which = np.where(hot,
                     n_channels + rng.integers(0, len(HOT_CHANNELS), n),
                     rng.integers(0, n_channels, n)).tolist()
    date_time = int(m["base_time_ms"]) + event * 1000 // int(m["events_per_s"])
    # filler: the source pads to an average record size, +- 20 %
    want = int(m["avg_bid_byte_size"]) - 32
    delta = int(round(want * 0.2))
    size = want - delta + rng.integers(0, max(2 * delta, 1), n)
    filler = bytes(rng.integers(97, 123, 1 << 20, dtype=np.uint8))
    start = rng.integers(0, len(filler) - int(size.max()), n)
    rows = [ROW % (a, b, p, channels[c], urls[c], t, filler[s:s + z])
            for a, b, p, c, t, s, z in zip(
                auction.tolist(), bidder.tolist(), price.tolist(), which,
                date_time.tolist(), start.tolist(), size.tolist())]
    drains = [rows[i:i + drain_rows] for i in range(0, n, drain_rows)]
    ids, keys = np.unique(bidder, return_inverse=True)
    return Pool(drains, keys.reshape(-1, drain_rows).astype(np.int64),
                price.reshape(-1, drain_rows), drain_rows, len(ids), ids)
