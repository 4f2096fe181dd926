"""The NEXmark event stream (Tucker, Tufte, Papadimos, Maier, "NEXMark - A
Benchmark for Queries over Data Streams", 2008) by the rules of the Apache
Beam Nexmark suite's generator (``GeneratorConfig``, ``PersonGenerator``,
``AuctionGenerator``, ``BidGenerator``, ``PriceGenerator``) at the
``NexmarkConfiguration`` defaults the configuration states.

Every event has a ``person``: a person's own id, an auction's seller, a
bid's bidder.  Columns: ``type`` (PERSON, AUCTION, BID), ``person``,
``auction``, ``price`` (cents) and ``date_time`` (integer ms from 0).  The
random draws come from the run's seed (numpy, not Java's ``Random``); the
proportions and the hot keys are deterministic in the event id, which
``carry`` holds from block to block.
"""
import numpy as np

#: Beam ``GeneratorConfig``: the first person and auction ids, and how far
#: past the newest id a random draw may reach
FIRST_PERSON_ID = 1000
FIRST_AUCTION_ID = 1000
PERSON_ID_LEAD = 10
AUCTION_ID_LEAD = 10
#: Beam ``AuctionGenerator.HOT_SELLER_RATIO`` and
#: ``BidGenerator.HOT_BIDDER_RATIO`` / ``HOT_AUCTION_RATIO``: the hot key is
#: the first (second, for bidders) of each batch of 100 newest ids
HOT_BATCH = 100


def types(gen):
    return list(gen["types"])


def last_person(g, eid):
    """``GeneratorConfig.lastBase0PersonId``: the newest person id (base 0)
    at event ``eid``."""
    P = g["person_proportion"]
    total = P + g["auction_proportion"] + g["bid_proportion"]
    epoch, off = np.divmod(eid, total)
    return epoch * P + np.where(off >= P, P - 1, off)


def last_auction(g, eid):
    """``GeneratorConfig.lastBase0AuctionId``: the newest auction id."""
    P, A = g["person_proportion"], g["auction_proportion"]
    total = P + A + g["bid_proportion"]
    epoch, off = np.divmod(eid, total)
    epoch = np.where(off < P, epoch - 1, epoch)
    off = np.where((off < P) | (off >= P + A), A - 1, off - P)
    return epoch * A + off


def random_person(g, rng, eid):
    """``GeneratorConfig.nextBase0PersonId``: uniform over the
    ``num_active_people`` newest persons and ``PERSON_ID_LEAD`` past them."""
    n_people = last_person(g, eid) + 1
    active = np.minimum(n_people, g["num_active_people"])
    return n_people - active + (rng.random(len(eid))
                                * (active + PERSON_ID_LEAD)).astype(np.int64)


def random_auction(g, rng, eid):
    """``BidGenerator.nextBase0AuctionId``: uniform over the
    ``num_in_flight_auctions`` newest auctions and ``AUCTION_ID_LEAD`` past
    them."""
    hi = last_auction(g, eid)
    lo = np.maximum(hi - g["num_in_flight_auctions"], 0)
    return lo + (rng.random(len(eid))
                 * (hi - lo + 1 + AUCTION_ID_LEAD)).astype(np.int64)


def price(rng, n):
    """``PriceGenerator.nextPrice``: ``round(10^(6u) * 100)`` cents."""
    return np.round(10.0 ** (rng.random(n) * 6.0) * 100.0).astype(np.int64)


def draw(s, n):
    g, rng = s.gen, s.rng
    e0 = int(s.carry.get("event_id", 0))
    s.carry["event_id"] = e0 + n
    eid = e0 + np.arange(n, dtype=np.int64)
    P, A = g["person_proportion"], g["auction_proportion"]
    # GeneratorConfig.nextEvent: the kind is eventId mod the proportions' sum
    off = eid % (P + A + g["bid_proportion"])
    kind = np.where(off < P, 0, np.where(off < P + A, 1, 2))
    newest = last_person(g, eid)
    # AuctionGenerator.nextAuction: the hot seller with probability
    # 1 - 1/hotSellersRatio (nextInt(ratio) > 0), else a random person
    hot_seller = rng.integers(0, g["hot_sellers_ratio"], n) > 0
    seller = np.where(hot_seller, newest // HOT_BATCH * HOT_BATCH,
                      random_person(g, rng, eid))
    # BidGenerator.nextBid: the hot bidder (second of the batch, so hot
    # bidders and hot sellers differ) with probability 1 - 1/hotBiddersRatio
    hot_bidder = rng.integers(0, g["hot_bidders_ratio"], n) > 0
    bidder = np.where(hot_bidder, newest // HOT_BATCH * HOT_BATCH + 1,
                      random_person(g, rng, eid))
    hot_auction = rng.integers(0, g["hot_auction_ratio"], n) > 0
    bid_auction = np.where(hot_auction,
                           last_auction(g, eid) // HOT_BATCH * HOT_BATCH,
                           random_auction(g, rng, eid))
    person = np.where(kind == 0, newest, np.where(kind == 1, seller, bidder))
    auction = np.where(kind == 1, last_auction(g, eid),
                       np.where(kind == 2, bid_auction, -1))
    # a bid's price, an auction's initial bid (PriceGenerator both); a
    # person has none
    cents = np.where(kind == 0, 0, price(rng, n))
    # GeneratorConfig.timestampForEvent: interEventDelayUs = 1e6 / rate,
    # in whole ms from the first event
    delay_us = 1e6 / float(g["events_per_sec"])
    return {
        "type": kind,
        "person": (person + FIRST_PERSON_ID).astype(np.int32),
        "auction": np.where(auction >= 0, auction + FIRST_AUCTION_ID,
                            -1).astype(np.int32),
        "price": cents,
        "date_time": np.floor(eid * delay_us / 1000.0).astype(np.int64),
    }
